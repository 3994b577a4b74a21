"""Smoke run of the approximate LM path on a TPU, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the (2, 2) mesh phase alone

One chip, at the full width of smollm-135m with the ``mul8s_1L2H``
multiplier in LUT mode:

1. kernels: ``fused_lut_dense`` (256x576 @ 576x1536), ``fused_lut_bwd`` and
   the contiguous and paged approximate attention kernels at smollm's head
   geometry, each compared bitwise with its jnp oracle on the chip;
2. serve: the serving launcher's own functions answer 8 requests of 16 new
   tokens through the paged continuous engine;
3. train: the training launcher's ``train`` takes 3 QAT steps (approximate
   forward and backward) at the full 49152-token vocabulary.

It prints every resolved plan (and fails unless each is a fused kernel
route), wall time per phase with compile time apart, the persistent
compile-cache hits, the tokens of each request and the losses. The last
line is one JSON object, ``{"ok": true, "device": {...}}``.

``--chips 4`` runs only the mesh phase on a (2, 2) ("data", "model") mesh:
a sharded ``approx_dense`` against the one-chip result, and one
data-parallel QAT step against the single-device oracle of that step.

Without a TPU the script exits with status 2 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m"
APPROX = "mul8s_1L2H:lut"
REQUESTS, NEW_TOKENS = 8, 16              # serve phase
STEPS, BATCH, SEQ = 3, 8, 256             # train phase
MESH_LAYERS, MESH_SEQ = 2, 128            # data-parallel step on (2, 2)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileLog:
    """Time spent tracing, lowering and compiling, and persistent
    compile-cache hits and misses, from JAX's monitoring events. Nested
    events (an inner jit traced inside an outer one) overlap, so the
    compile time is the length of the union of their intervals."""

    def __init__(self):
        import jax
        self.spans: list[tuple[float, float]] = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            end = time.monotonic()
            self.spans.append((end - secs, end))

    def seconds(self, since: float = 0.0) -> float:
        total, reach = 0.0, since
        for lo, hi in sorted(self.spans):
            lo = max(lo, reach)
            if hi > lo:
                total += hi - lo
                reach = hi
        return total

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@contextlib.contextmanager
def phase(name: str, log: CompileLog):
    t0, h0 = time.monotonic(), log.hits
    yield
    wall, comp = time.monotonic() - t0, log.seconds(since=t0)
    print(f"phase {name}: wall {wall:.3f}s, compile {comp:.3f}s, "
          f"rest {wall - comp:.3f}s, cache hits {log.hits - h0}", flush=True)


def compare(name: str, got, want) -> bool:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    same = got.shape == want.shape and np.array_equal(got, want)
    diff = (int(np.sum(got != want)), float(np.max(np.abs(got - want))))
    print(f"check {name}: shape {got.shape} bitwise {'equal' if same else 'DIFFERENT'}"
          f" (differing {diff[0]}, max |diff| {diff[1]!r}, finite "
          f"{bool(np.isfinite(got).all())})", flush=True)
    return same and bool(np.isfinite(got).all())


def kernel_checks(seed: int) -> bool:
    """The main-path kernels against their jnp oracles, bitwise."""
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.acu import make_acu
    from repro.core.quantization import inline_symmetric_scale
    from repro.kernels.flash_attention.approx import (
        approx_flash_attention, approx_flash_attention_paged)
    from repro.kernels.flash_attention.ref import (approx_attention_paged_ref,
                                                   approx_attention_ref)
    from repro.kernels.fused_lut_dense.ops import fused_lut_bwd, fused_lut_dense
    from repro.kernels.fused_lut_dense.ref import (fused_lut_bwd_ref,
                                                   fused_lut_dense_ref)

    cfg = get_config(ARCH)
    acu = make_acu(APPROX.split(":")[0], "lut")
    lut, off, n = jnp.asarray(acu.lut), acu.offset, acu.multiplier.n_codes
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    def scale(t):
        return inline_symmetric_scale(jnp.max(jnp.abs(t)), 8)

    ok = True
    d, f = cfg.d_model, cfg.d_ff
    x, ws = normal(256, d), jnp.abs(normal(f)) * 0.01 + 1e-3
    wq = jnp.asarray(rng.integers(-127, 128, (d, f)), jnp.int32)
    xs = scale(x)
    ok &= compare("fused_lut_dense 256x576x1536",
                  fused_lut_dense(x, wq, lut, off, xs, 0.0, ws),
                  fused_lut_dense_ref(x, wq, lut.reshape(-1), off, n, xs, 0.0,
                                      ws))
    a, b = normal(256, f), normal(f, d)
    ok &= compare("fused_lut_bwd 256x1536x576",
                  fused_lut_bwd(a, b, lut, off, scale(a), scale(b)),
                  fused_lut_bwd_ref(a, b, lut.reshape(-1), off, n, scale(a),
                                    scale(b)))

    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = normal(hq, 256, hd), normal(hkv, 256, hd), normal(hkv, 256, hd)
    sc = [scale(t) for t in (q, k, v)]
    ok &= compare(f"approx_flash_attention {hq}x256x{hd} (kv heads {hkv})",
                  approx_flash_attention(q, k, v, lut, off, *sc),
                  approx_attention_ref(q, k, v, lut, off, *sc))

    rows, blk, n_log = 4, 16, 16                  # decode: 4 slots, 256 max
    pool_k, pool_v = normal(hkv, 4 * n_log, blk, hd), normal(hkv, 4 * n_log,
                                                             blk, hd)
    pt = np.stack([rng.permutation(4 * n_log)[:n_log] for _ in range(rows)])
    kv_len = rng.integers(1, n_log * blk + 1, rows)
    info = np.stack([kv_len - 1, np.zeros(rows), kv_len], 1)
    pt = jnp.asarray(np.repeat(pt, hq, 0), jnp.int32)
    info = jnp.asarray(np.repeat(info, hq, 0), jnp.int32)
    qd = normal(rows * hq, 1, hd)
    sc = [scale(t) for t in (qd, pool_k, pool_v)]
    kw = dict(rowinfo=info, page_table=pt, rep=hq // hkv)
    ok &= compare(f"approx_flash_attention_paged decode {rows}x{hq}x1x{hd}",
                  approx_flash_attention_paged(qd, pool_k, pool_v, lut, off,
                                               *sc, **kw),
                  approx_attention_paged_ref(qd, pool_k, pool_v, lut, off,
                                             *sc, **kw))
    return ok


def plans_ok(plans, want_attn: str | None, want_bwd: bool) -> bool:
    """Print the plans a phase resolved; True when each is a fused kernel
    route."""
    ok, seen_attn, seen_bwd = True, False, False
    for p in plans:
        print(f"plan {json.dumps(p, default=str)}")
        if p["op"] == "gemm":
            ok &= p["route"] == "fused_lut_dense"
            seen_bwd |= p["bwd_route"] == "fused_lut_bwd"
            if want_bwd:
                ok &= p["bwd_route"] == "fused_lut_bwd"
        else:
            ok &= p["route"] in ("fused_attn", "fused_attn_paged")
            seen_attn |= p["route"] == want_attn
    return ok and (want_attn is None or seen_attn) and (seen_bwd or not want_bwd)


def serve_phase(log: CompileLog, seed: int) -> bool:
    from repro.launch.serve import (attn_plan_report, build_engine,
                                    make_requests, serve)
    from repro.core.approx_ops import resolved_plans
    n0 = len(resolved_plans())
    with phase("serve", log):
        eng = build_engine(ARCH, approx=APPROX, paged=True, seed=seed)
        reqs = make_requests(eng.cfg.vocab_size, REQUESTS, NEW_TOKENS, seed)
        done, secs = serve(eng, reqs)
    print(f"attn_plan {json.dumps(attn_plan_report(eng), default=str)}")
    vocab = eng.cfg.vocab_size
    ok = len(done) == REQUESTS
    for i, r in enumerate(done):
        out = [int(t) for t in r.out]
        ok &= len(out) == NEW_TOKENS and all(0 <= t < vocab for t in out)
        print(f"request {i}: prompt {len(r.prompt)} tokens -> {len(out)} "
              f"tokens {out}")
    print(f"served {sum(len(r.out) for r in done)} tokens in {secs:.3f}s; "
          f"stats {eng.stats}")
    return plans_ok(resolved_plans()[n0:], "fused_attn_paged",
                    want_bwd=False) and ok


def train_phase(log: CompileLog, seed: int) -> bool:
    import math

    from repro.core.approx_ops import resolved_plans
    from repro.launch.train import train
    n0 = len(resolved_plans())
    with phase("train", log):
        trainer = train(ARCH, steps=STEPS, batch=BATCH, seq=SEQ,
                        approx=APPROX, log_every=1, seed=seed)
    losses = [h["loss"] for h in trainer.history if "loss" in h]
    for h in trainer.history:
        print(f"train {h}")
    ok = len(losses) == STEPS and all(math.isfinite(l) for l in losses)
    print(f"losses {losses}")
    return plans_ok(resolved_plans()[n0:], None, want_bwd=True) and ok


def mesh_phase(log: CompileLog, seed: int) -> bool:
    """Sharded approx_dense and one data-parallel QAT step on (2, 2),
    each against its single-device counterpart."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.approx_ops import approx_dense
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import make_acfg
    from repro.models.transformer import init_params, loss_fn
    from repro.optim.adamw import AdamW
    from repro.optim.compression import compress
    from repro.parallel.sharding import use_mesh
    from repro.train.trainer import Trainer, TrainerConfig

    mesh = make_mesh((2, 2), ("data", "model"))
    cfg = get_config(ARCH)
    rng = np.random.default_rng(seed)
    ok = True

    with phase("mesh approx_dense", log):
        acfg = make_acfg(APPROX)
        x = jnp.asarray(rng.normal(size=(256, cfg.d_model)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(cfg.d_model, cfg.d_ff)) * 0.05,
                        jnp.float32)
        dense = jax.jit(lambda x, w: approx_dense(x, w, None, acfg))
        one = dense(x, w)
        with use_mesh(mesh):
            sharded = jax.jit(lambda x, w: approx_dense(x, w, None, acfg))(
                x, w)
        ok &= compare(f"approx_dense 256x{cfg.d_model}x{cfg.d_ff} (2, 2) mesh"
                      f" vs one chip", sharded, one)

    with phase("mesh dp QAT step", log):
        cfg = dataclasses.replace(cfg, n_layers=MESH_LAYERS)
        acfg = make_acfg(APPROX, approx_bwd=True)
        params = init_params(jax.random.PRNGKey(seed), cfg)
        toks = rng.integers(0, cfg.vocab_size,
                            (BATCH, MESH_SEQ + 1)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks[:, :-1]),
                 "labels": jnp.asarray(toks[:, 1:])}

        def loss(p, b):
            return loss_fn(p, b["tokens"], b["labels"], cfg, acfg)

        opt = AdamW(lr=1e-3)
        tr = Trainer(loss, opt, TrainerConfig(mesh=mesh), donate=False)
        p_mesh, o_mesh, loss_mesh, _ = tr._run_step(
            params, opt.init(params), batch, n_micro=1)

        # single-device oracle: per-worker grads, shared-amax int8 codes,
        # int32 code sum x scale / W, the same AdamW update
        n_dp = mesh.shape["data"]
        grad_fn = jax.jit(jax.value_and_grad(loss))
        rows = BATCH // n_dp
        per = [grad_fn(params, {k: v[i * rows:(i + 1) * rows]
                                for k, v in batch.items()})[1]
               for i in range(n_dp)]
        leaves = [jax.tree.leaves(g) for g in per]
        mean = []
        for li in range(len(leaves[0])):
            gs = [lv[li].astype(jnp.float32) for lv in leaves]
            amax = jnp.max(jnp.stack([jnp.max(jnp.abs(g)) for g in gs]))
            qs = [compress(g, amax) for g in gs]
            q_sum = sum(q[0].astype(jnp.int32) for q in qs)
            mean.append(q_sum.astype(jnp.float32) * (qs[0][1] / n_dp))
        mean = jax.tree.unflatten(jax.tree.structure(per[0]), mean)
        p_one, _ = jax.jit(opt.update)(mean, opt.init(params), params)
        print(f"mesh dp loss {float(loss_mesh)!r}")
        names = [jax.tree_util.keystr(k) for k, _ in
                 jax.tree_util.tree_flatten_with_path(p_mesh)[0]]
        for name, a, b in zip(names, jax.tree.leaves(p_mesh),
                              jax.tree.leaves(p_one)):
            ok &= compare(f"dp QAT step params{name}", a, b)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.kernels.runtime import enable_compile_cache, resolve_interpret
    cache_dir = enable_compile_cache()
    log = CompileLog()
    kind = devices[0].device_kind
    print(f"device {kind} x{len(devices)}; kernels "
          f"{'interpreted' if resolve_interpret(None) else 'compiled'}; "
          f"compile cache {cache_dir}", flush=True)

    t0 = time.monotonic()
    if args.chips == 4:
        ok = mesh_phase(log, args.seed)
    else:
        with phase("kernel checks", log):
            ok = kernel_checks(args.seed)
        ok &= serve_phase(log, args.seed)
        ok &= train_phase(log, args.seed)
    print(f"total wall {time.monotonic() - t0:.3f}s, compile "
          f"{log.seconds():.3f}s, persistent cache hits {log.hits}, "
          f"misses {log.misses}")
    if not ok:
        print("FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
