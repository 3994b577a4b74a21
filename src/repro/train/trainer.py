"""Fault-tolerant training loop.

Features (DESIGN.md §5):
* jit'd train step with planner-driven in/out shardings and donated buffers,
* gradient accumulation (microbatching) via ``lax.scan`` over microbatches,
* gradient-noise batch damping (``optim/damping.py``): the effective batch
  grows — by accumulating whole data batches per optimizer step — as the
  measured gradient noise scale rises during QAT recovery; the per-microbatch
  (or per-mesh-shard) gradient norms the loop already computes feed the
  estimator for free,
* an explicit-collective data-parallel path (``TrainerConfig.mesh``): each
  worker grads its batch shard inside ``shard_map``, gradients all-reduce
  through the int8 error-feedback ``compressed_psum`` — whose int32 code
  psum makes the mean bitwise independent of reduction order — and the
  optimizer update runs on the replicated mean,
* periodic async checkpointing; automatic restore-and-continue on failure
  (exceptions from steps — simulating node loss — roll back to the last
  checkpoint; validated by tests/test_fault_tolerance.py). Resume is
  DETERMINISTIC: the manifest records the consumed-batch count (plus the
  damping-schedule state and the dp error-feedback residual), batches drawn
  since the last durable checkpoint replay from a bounded buffer after an
  in-process rollback, and a fresh restart fast-forwards its iterator to the
  recorded count — so a killed-and-resumed run reproduces the uninterrupted
  run exactly,
* step-time watchdog hook (straggler posture),
* QAT mode: the same loop fine-tunes through the approximate forward / exact
  STE backward (paper Fig. 1 flow),
* profiler spans on the host: each loop iteration is a ``repro.train.step``
  step span holding ``repro.train.draw`` (the next batch), ``repro.train.wait``
  (the host reading the loss, i.e. waiting for the device), and
  ``repro.train.checkpoint`` / ``repro.train.restore`` when those run. They
  record nothing unless a profiler trace is active. The device ops carry the
  model's ``jax.named_scope`` names (``embed``, ``attn``, ``mlp``, ``moe``,
  ``final_norm``, ``lm_head``, ``loss``) and ``optimizer`` for the update.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.optim import damping as damping_lib
from repro.optim.adamw import AdamW, SGD
from repro.train import checkpoint as ckpt_lib


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    microbatch: int = 0          # 0 = no accumulation (fixed split of a batch)
    max_failures: int = 3
    step_timeout_s: Optional[float] = None   # watchdog (logged, not killed)
    log_every: int = 10
    async_ckpt: bool = True
    # gradient-noise batch damping: when set, each optimizer step consumes
    # ``accum`` whole data batches (the schedule grows accum as gradients
    # denoise); mutually exclusive with a fixed ``microbatch``.
    damping: Optional[damping_lib.DampingConfig] = None
    # explicit-collective data parallelism: the batch shards over ``dp_axes``
    # of ``mesh``; per-worker grads all-reduce via the int8 error-feedback
    # compressed psum (optim/compression.py) whose int32 code sum keeps the
    # mean bitwise reduction-order independent.
    mesh: Optional[object] = None
    dp_axes: tuple[str, ...] = ("data",)


class Trainer:
    """Drives (params, opt_state) through a loss function with recovery."""

    def __init__(self, loss_fn: Callable, optimizer: AdamW | SGD,
                 cfg: TrainerConfig = TrainerConfig(), *,
                 in_shardings=None, donate: bool = True):
        if cfg.damping is not None and cfg.microbatch > 1:
            raise ValueError("damping drives the accumulation factor itself; "
                             "set microbatch=0 when damping is enabled")
        self.loss_fn = loss_fn
        self.opt = optimizer
        self.cfg = cfg
        self.saver = ckpt_lib.AsyncSaver()
        self.history: list[dict] = []
        self._donate = donate
        self._steps: dict[int, Callable] = {}   # jit cache keyed by n_micro
        self._ef_resid = None                   # dp error-feedback residual
        if cfg.mesh is not None:
            import numpy as np
            self._dp_workers = int(np.prod(
                [cfg.mesh.shape[a] for a in cfg.dp_axes]))
        else:
            self._dp_workers = 1

    # ------------------------------------------------------------------
    # step construction (one jit cache entry per accumulation factor)
    # ------------------------------------------------------------------

    def _get_step(self, n_micro: int) -> Callable:
        fn = self._steps.get(n_micro)
        if fn is None:
            fn = (self._build_dp_step(n_micro) if self.cfg.mesh is not None
                  else self._build_step(n_micro))
            self._steps[n_micro] = fn
        return fn

    def _grads_and_stats(self, params, batch, n_micro: int):
        """loss, mean grads, and the scan-accumulated sum of per-microbatch
        |g|^2 (the damping estimator's small-batch side, free in the scan).

        ``batch`` leaves are ``(n_micro, b, ...)`` when ``n_micro > 1``
        (stacked microbatches), flat otherwise. The scan carry is pinned to
        fp32 — a weak-typed ``0.0`` loss accumulator used to let the loss
        dtype leak into the carry.
        """
        loss_fn = self.loss_fn
        if n_micro > 1:
            def micro(carry, mb):
                loss, grads = jax.value_and_grad(loss_fn)(params, mb)
                l0, g0, sq0 = carry
                return (l0 + loss.astype(jnp.float32),
                        jax.tree.map(jnp.add, g0, grads),
                        sq0 + damping_lib.tree_sqnorm(grads)), None
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
            carry0 = (jnp.zeros((), jnp.float32), zero,
                      jnp.zeros((), jnp.float32))
            (loss, gsum, sqsum), _ = jax.lax.scan(micro, carry0, batch)
            loss = loss / n_micro
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            micro_sqsum = sqsum
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            micro_sqsum = damping_lib.tree_sqnorm(grads)
        return loss, grads, micro_sqsum

    def _build_step(self, n_micro: int) -> Callable:
        def step_fn(params, opt_state, batch):
            loss, grads, micro_sqsum = self._grads_and_stats(
                params, batch, n_micro)
            stats = {"micro_sqsum": micro_sqsum,
                     "gsq_big": damping_lib.tree_sqnorm(grads)}
            with jax.named_scope("optimizer"):
                new_params, new_state = self.opt.update(grads, opt_state,
                                                        params)
            return new_params, new_state, loss, stats

        donate = (0, 1) if self._donate else ()
        return jax.jit(step_fn, donate_argnums=donate)

    def _build_dp_step(self, n_micro: int) -> Callable:
        from jax.sharding import PartitionSpec as P

        from repro.optim.compression import EFState, compressed_psum

        cfg = self.cfg
        axes = cfg.dp_axes
        ax = axes if len(axes) > 1 else axes[0]
        p_lead = P(ax)                         # shard leading dim (resid)
        p_batch = P(None, ax) if n_micro > 1 else P(ax)

        def worker(params, resid, batch):
            loss, grads, micro_sqsum = self._grads_and_stats(
                params, batch, n_micro)
            resid = jax.tree.map(lambda r: r[0], resid)
            mean, ef = compressed_psum(grads, EFState(residual=resid), axes)
            new_resid = jax.tree.map(lambda r: r[None], ef.residual)
            loss = jax.lax.pmean(loss, axes)
            # per-worker scalars leave SHARDED: the host folds them in a
            # fixed order (fp64), so the damping schedule never depends on
            # the collective's float reduction order
            one = lambda x: jnp.reshape(x, (1,))
            return (mean, new_resid, loss,
                    one(damping_lib.tree_sqnorm(grads)),
                    one(damping_lib.tree_sqnorm(ef.residual)))

        sharded = jax.shard_map(
            worker, mesh=cfg.mesh,
            in_specs=(P(), p_lead, p_batch),
            out_specs=(P(), p_lead, P(), p_lead, p_lead),
            check_vma=False)

        def step_fn(params, opt_state, resid, batch):
            mean, new_resid, loss, local_sq, resid_sq = sharded(
                params, resid, batch)
            # |mean|^2 on the replicated mean: identical reduction order on
            # every worker and in the single-device oracle
            stats = {"local_sq": local_sq, "resid_sq": resid_sq,
                     "gsq_big": damping_lib.tree_sqnorm(mean)}
            with jax.named_scope("optimizer"):
                new_params, new_state = self.opt.update(mean, opt_state,
                                                        params)
            return new_params, new_state, new_resid, loss, stats

        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(step_fn, donate_argnums=donate)

    def _init_ef(self, params):
        w = self._dp_workers
        return jax.tree.map(
            lambda p: jnp.zeros((w,) + tuple(p.shape), jnp.float32), params)

    # ------------------------------------------------------------------
    # checkpoint state (dp runs carry the EF residual in the snapshot:
    # exact resume needs exactly what the optimizer hasn't seen yet)
    # ------------------------------------------------------------------

    def _ckpt_tree(self, params, opt_state):
        if self.cfg.mesh is not None:
            return (params, opt_state, self._ef_resid)
        return (params, opt_state)

    def _unpack_ckpt(self, tree):
        if self.cfg.mesh is not None:
            params, opt_state, self._ef_resid = tree
            return params, opt_state
        return tree

    def restore_or_init(self, params, opt_state):
        """Returns ``(params, opt_state, start_step, manifest_extra)``; the
        extra dict carries the consumed-batch count and damping state."""
        c = self.cfg
        if c.mesh is not None and self._ef_resid is None:
            self._ef_resid = self._init_ef(params)
        if c.ckpt_dir:
            step = ckpt_lib.latest_step(c.ckpt_dir)
            if step is not None:
                tree, man = ckpt_lib.restore(
                    c.ckpt_dir, step, self._ckpt_tree(params, opt_state))
                params, opt_state = self._unpack_ckpt(tree)
                return params, opt_state, man["step"], man.get("extra", {})
        return params, opt_state, 0, {}

    # ------------------------------------------------------------------

    def fit(self, params, opt_state, batches: Iterator[dict], n_steps: int,
            *, fail_hook: Optional[Callable[[int], None]] = None,
            step_hook: Optional[Callable] = None):
        """Run ``n_steps``; on step failure restore the last checkpoint and
        continue (up to cfg.max_failures) — deterministically: rolled-back
        batches replay from the buffer, so the resumed run is bitwise the
        run that never failed."""
        c = self.cfg
        params, opt_state, start, extra = self.restore_or_init(
            params, opt_state)
        step = start
        consumed = int(extra.get("consumed", 0))
        damp = None
        if c.damping is not None:
            damp = (damping_lib.DampingState.from_dict(extra["damping"])
                    if extra.get("damping") else
                    damping_lib.init_state(c.damping))

        it = iter(batches)
        for _ in range(consumed):     # fresh-restart fast-forward: skip
            next(it)                  # batches the checkpoint already trained on
        replay_buf: list[tuple[int, dict]] = []   # since last durable ckpt
        replay_pending: list[tuple[int, dict]] = []
        saved_consumed: dict[int, int] = {}       # ckpt step -> consumed
        if c.ckpt_dir and start > 0:
            saved_consumed[start] = consumed

        def draw():
            nonlocal consumed
            if replay_pending:
                idx, b = replay_pending.pop(0)
                assert idx == consumed, (idx, consumed)
            else:
                b = next(it)
                if c.ckpt_dir:   # no ckpt -> no rollback -> no replay need
                    replay_buf.append((consumed, b))
            consumed += 1
            return b

        def trim_replay():
            durable = (self.saver.last_saved_step if c.async_ckpt
                       else max(saved_consumed, default=None))
            if durable is None or durable not in saved_consumed:
                return
            keep_from = saved_consumed[durable]
            while replay_buf and replay_buf[0][0] < keep_from:
                replay_buf.pop(0)

        failures = 0
        while step < n_steps:
            with StepTraceAnnotation("repro.train.step", step_num=step):
                with TraceAnnotation("repro.train.draw"):
                    n_micro, batch, batch_rows = self._next_batch(draw, damp)
                t0 = time.monotonic()
                try:
                    if fail_hook is not None:
                        fail_hook(step)  # failure injection point (tests)
                    params, opt_state, loss, stats = self._run_step(
                        params, opt_state, batch, n_micro)
                    with TraceAnnotation("repro.train.wait"):
                        loss = float(loss)
                except Exception as e:  # noqa: BLE001 — node-failure surface
                    failures += 1
                    if failures > c.max_failures or not c.ckpt_dir:
                        raise
                    with TraceAnnotation("repro.train.restore"):
                        self.saver.wait()   # in-flight snapshot is durable
                        restored = ckpt_lib.latest_step(c.ckpt_dir)
                        if restored is None:
                            raise RuntimeError(
                                "failure before first checkpoint") from e
                        tree, man = ckpt_lib.restore(
                            c.ckpt_dir, restored,
                            jax.tree.map(lambda x: x,
                                         self._ckpt_tree(params, opt_state)))
                        params, opt_state = self._unpack_ckpt(tree)
                        step = man["step"]
                        extra = man.get("extra", {})
                        back_to = int(extra.get("consumed", 0))
                        if damp is not None:
                            damp = (damping_lib.DampingState.from_dict(
                                extra["damping"]) if extra.get("damping") else
                                damping_lib.init_state(c.damping))
                        # rewind: every batch drawn after the checkpoint
                        # replays, in draw order (replay_buf is append-ordered
                        # and never re-appends a replayed batch, so this
                        # filter is exact)
                        replay_pending = [(i, b) for i, b in replay_buf
                                          if i >= back_to]
                        consumed = back_to
                    self.history.append(
                        {"step": step,
                         "event": f"restored after {type(e).__name__}"})
                    continue
                dt = time.monotonic() - t0
                step += 1
                if step_hook is not None:   # eval/curve hook (benchmarks)
                    step_hook(step, params, consumed)
                if damp is not None and step % c.damping.check_every == 0:
                    damp = self._damping_update(damp, stats, n_micro,
                                                batch_rows)
                if c.step_timeout_s and dt > c.step_timeout_s:
                    self.history.append(
                        {"step": step, "event": f"straggler: {dt:.1f}s"})
                if step % c.log_every == 0 or step == n_steps:
                    h = {"step": step, "loss": loss, "dt": dt,
                         "consumed": consumed}
                    if damp is not None:
                        h.update(accum=damp.accum, b_noise=damp.b_noise)
                    self.history.append(h)
                if c.ckpt_dir and (step % c.ckpt_every == 0
                                   or step == n_steps):
                    extra_out = {"consumed": consumed}
                    if damp is not None:
                        extra_out["damping"] = damp.to_dict()
                    saved_consumed[step] = consumed
                    with TraceAnnotation("repro.train.checkpoint"):
                        snapshot = self._ckpt_tree(params, opt_state)
                        if c.async_ckpt:
                            self.saver.submit(c.ckpt_dir, step, snapshot,
                                              extra=extra_out, keep=c.keep)
                        else:
                            ckpt_lib.save(c.ckpt_dir, step, snapshot,
                                          extra=extra_out, keep=c.keep)
                    trim_replay()
        self.saver.wait()
        self.consumed = consumed
        self.damp_state = damp
        return params, opt_state

    # ------------------------------------------------------------------
    # batch shaping + damping plumbing
    # ------------------------------------------------------------------

    def _next_batch(self, draw, damp):
        """Draw and shape the next step's input.

        Returns ``(n_micro, batch, batch_rows)`` where ``batch_rows`` is the
        row count of ONE drawn data batch (the unit the damping schedule
        multiplies by ``accum``).
        """
        c = self.cfg
        if damp is None:
            batch = draw()
            rows = _leading_rows(batch)
            k = c.microbatch if c.microbatch and c.microbatch > 1 else 1
            if k > 1:
                batch = _split_micro(batch, k)
            return k, batch, rows
        if damp.accum == 1:
            batch = draw()
            rows = _leading_rows(batch)
            if rows % 2 == 0:   # free noise pair: split the batch in two
                return 2, _split_micro(batch, 2), rows
            return 1, batch, rows
        drawn = [draw() for _ in range(damp.accum)]
        rows = _leading_rows(drawn[0])
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *drawn)
        return damp.accum, batch, rows

    def _run_step(self, params, opt_state, batch, n_micro):
        step = self._get_step(n_micro)
        if self.cfg.mesh is not None:
            if self._ef_resid is None:
                self._ef_resid = self._init_ef(params)
            params, opt_state, self._ef_resid, loss, stats = step(
                params, opt_state, self._ef_resid, batch)
            return params, opt_state, loss, stats
        return step(params, opt_state, batch)

    def _damping_update(self, damp, stats, n_micro, batch_rows):
        import numpy as np
        c = self.cfg
        total = batch_rows * (damp.accum if damp.accum > 1 else 1)
        if self.cfg.mesh is not None:
            # mesh pair: per-worker shard grads vs the psum'd mean; fold the
            # per-worker scalars on the host in index order (fp64)
            w = self._dp_workers
            if total % w != 0 or total // w == total:
                return damp
            st = damping_lib.NoiseStats(
                gsq_small=float(np.asarray(stats["local_sq"],
                                           np.float64).sum() / w),
                gsq_big=float(stats["gsq_big"]),
                b_small=total // w, b_big=total,
                resid_sq=float(np.asarray(stats["resid_sq"],
                                          np.float64).sum() / w))
            return damping_lib.update_state(damp, c.damping, st, batch_rows)
        if n_micro < 2:
            return damp    # no pair this step (odd batch at accum=1)
        st = damping_lib.NoiseStats(
            gsq_small=float(stats["micro_sqsum"]) / n_micro,
            gsq_big=float(stats["gsq_big"]),
            b_small=total // n_micro, b_big=total)
        return damping_lib.update_state(damp, c.damping, st, batch_rows)


def _leading_rows(batch) -> int:
    return int(jax.tree.leaves(batch)[0].shape[0])


def _split_micro(batch, k: int):
    """Reshape a flat batch into ``k`` stacked microbatches, validating
    divisibility loudly (a silent ``reshape(k, -1, ...)`` used to accept —
    and misassemble — non-divisible batches)."""
    def one(x):
        if x.shape[0] % k != 0:
            raise ValueError(
                f"microbatch={k} does not divide batch dim {x.shape[0]} "
                f"(leaf shape {x.shape}); pick a divisor of the batch size")
        return x.reshape(k, x.shape[0] // k, *x.shape[1:])
    return jax.tree.map(one, batch)
