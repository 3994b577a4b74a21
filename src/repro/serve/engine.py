"""Serving engines: batched LM prefill/decode and batched vision inference.

Three LM engines share the jitted ``apply_model`` steps:

* :class:`ServeEngine` — *waves*: up to ``slots`` prompts are padded to a
  common length, prefilled in one batched call, then decoded in lockstep
  (one jit'd decode step per token for the whole batch). Per-request early
  stop masks finished rows, but a finished slot idles until the whole wave
  drains, and arrivals queue behind the current wave.
* :class:`ContinuousServeEngine` — true continuous batching: every slot
  advances at its *own* cache position (``cache_pos`` is a (slots,) vector;
  the KV append is a vmap'd per-row ``dynamic_update_slice``), a finished
  slot is evicted and refilled immediately (batch-1 bucketed prefill +
  jitted row insertion into the batched cache), so the decode batch stays
  full under load. Sustained tokens/s under a Poisson arrival trace is the
  ``[serve]`` benchmark's headline number.
* :class:`PagedContinuousServeEngine` — the same continuous scheduler over
  a block-paged KV cache (vLLM's PagedAttention is the exemplar): KV lives
  in fixed-size physical blocks handed out by a free-list
  :class:`BlockAllocator` under a global HBM budget, each slot addresses
  them through a per-slot page table, prompts prefill in block-aligned
  chunks, shared prompt prefixes become refcounted cache hits (full-block
  granularity, chained hashes, copy-on-write on the decode tail), and
  memory pressure is resolved by LRU prefix-cache eviction first,
  youngest-request preemption second — so admission is bounded by *blocks
  in use*, not slot count times ``max_seq``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.transformer import apply_model, init_cache, init_paged_cache
from repro.parallel.sharding import MeshContext, use_mesh, use_mesh_context


@dataclasses.dataclass
class Request:
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    out: Optional[np.ndarray] = None


class ServeEngine:
    """``mesh`` (a ``jax.sharding.Mesh`` or an existing
    :class:`~repro.parallel.sharding.MeshContext`) activates mesh-aware
    execution for both jits: prefill/decode trace under
    :func:`~repro.parallel.sharding.use_mesh`, so every ``matmul_plan``
    inside `apply_model` resolves to its sharded route (and the models'
    logical-axis ``shard()`` annotations become real constraints) instead of
    silently running replicated. ``mesh=None`` keeps the single-device
    behavior bit-for-bit."""

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 max_seq: int = 512, acfg=None, mesh=None):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        self.acfg = acfg
        if mesh is None:
            self._mesh_scope = contextlib.nullcontext
        elif isinstance(mesh, MeshContext):
            # verbatim: a context whose rules omit keys means "replicated
            # there" — re-entering via use_mesh would re-merge DEFAULT_RULES
            self._mesh_scope = lambda: use_mesh_context(mesh)
        else:
            self._mesh_scope = lambda: use_mesh(mesh)

        def prefill(params, cache, tokens, pos_offset, pad_mask):
            logits, cache = apply_model(params, tokens, cfg, acfg=acfg,
                                        cache=cache, cache_pos=0,
                                        pos_offset=pos_offset,
                                        pad_mask=pad_mask)
            return logits[:, -1], cache

        def decode(params, cache, tokens, pos, pos_offset, pad_mask):
            logits, cache = apply_model(params, tokens, cfg, acfg=acfg,
                                        cache=cache, cache_pos=pos, decode=True,
                                        pos_offset=pos_offset,
                                        pad_mask=pad_mask)
            return logits[:, -1], cache

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(1,))

    def _wave(self, reqs: list[Request],
              on_token: Optional[Callable[[int, int], None]]) -> None:
        b = self.slots
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((b, plen), np.int32)
        offs = np.zeros(b, np.int32)           # per-request left-pad counts
        valid = np.zeros((b, self.max_seq), bool)
        for i, r in enumerate(reqs):
            off = plen - len(r.prompt)
            toks[i, off:] = r.prompt           # left-pad
            offs[i] = off
            valid[i, off:] = True              # pad slots masked for the wave
        cache = init_cache(self.cfg, b, self.max_seq)
        offs_j, valid_j = jnp.asarray(offs), jnp.asarray(valid)
        with self._mesh_scope():
            logits, cache = self._prefill(self.params, cache,
                                          jnp.asarray(toks), offs_j, valid_j)
        cur = np.asarray(jnp.argmax(logits, -1))
        max_new = max(r.max_new_tokens for r in reqs)
        budget = max(0, min(max_new, self.max_seq - plen))
        out = np.zeros((b, budget), np.int32)  # preallocated (was O(n^2)
        n_out = np.zeros(b, np.int32)          # np.append per token)
        alive = np.ones(b, bool)
        for t in range(budget):
            for i in np.flatnonzero(alive):
                out[i, t] = cur[i]
                n_out[i] += 1
                if on_token:
                    on_token(int(i), int(cur[i]))
                if n_out[i] >= reqs[i].max_new_tokens:
                    alive[i] = False
            # no decode once every slot is done, nor for the step whose
            # logits nothing would consume (the old loop ran one extra)
            if not alive.any() or t == budget - 1:
                break
            with self._mesh_scope():
                logits, cache = self._decode(self.params, cache,
                                             jnp.asarray(cur)[:, None],
                                             plen + t, offs_j, valid_j)
            cur = np.asarray(jnp.argmax(logits, -1))
        for i, r in enumerate(reqs):
            r.out = out[i, :n_out[i]].copy()

    def run(self, requests: list[Request],
            on_token: Optional[Callable[[int, int], None]] = None) -> list[Request]:
        """Serve all requests (waves of ``slots``); returns them with .out."""
        reqs = list(requests)
        for i in range(0, len(reqs), self.slots):
            wave = reqs[i:i + self.slots]
            while len(wave) < self.slots:       # pad the wave with a dummy
                wave.append(Request(prompt=np.zeros(1, np.int32),
                                    max_new_tokens=1))
            self._wave(wave, on_token)
        return requests


def _bucket(n: int, lo: int = 8) -> int:
    """Next power-of-two >= n (>= lo): bounds prefill recompiles to log2
    distinct prompt shapes."""
    b = lo
    while b < n:
        b *= 2
    return b


def poisson_arrivals(n: int, rate: float, seed: int = 0) -> np.ndarray:
    """Cumulative Poisson-process arrival times for ``n`` requests, in
    decode-step units (``rate`` = mean arrivals per decode step)."""
    rng = np.random.RandomState(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


class ContinuousServeEngine:
    """Continuous-batching LM serving: slot-level admission and eviction.

    Each incoming request is prefilled alone (prompt left-padded to a
    power-of-two bucket, so at most log2(max_seq) prefill shapes compile),
    its batch-1 cache row is inserted into the live batched cache by a
    jitted ``dynamic_update_slice``, and from then on the slot decodes in
    the shared batched step at its own cache position — ``cache_pos`` is a
    (slots,) vector and every attention layer appends KV with a vmap'd
    per-row update. A slot that exhausts its ``max_new_tokens`` (honored
    exactly, per request) is evicted the same step and its slot refilled by
    the next queued arrival, so unlike the wave engine no row idles behind
    the longest request in its batch.

    ``run(requests, arrivals=None)``: ``arrivals`` are request arrival
    times in decode-step units (``None`` = all at t=0); the engine's clock
    is the decode-step counter, so a trace replays deterministically.
    ``self.stats`` afterwards holds ``decode_steps``, ``prefills``,
    ``tokens`` and mean slot ``occupancy`` per decode step.

    Same mesh contract as :class:`ServeEngine`; with a LUT-Pallas ``acfg``
    every attention layer rides the fused approximate flash kernel
    (per-row ``rowinfo`` built from the position vector and pad mask).
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 max_seq: int = 512, acfg=None, mesh=None):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        self.acfg = acfg
        self.stats: dict = {}
        if mesh is None:
            self._mesh_scope = contextlib.nullcontext
        elif isinstance(mesh, MeshContext):
            self._mesh_scope = lambda: use_mesh_context(mesh)
        else:
            self._mesh_scope = lambda: use_mesh(mesh)

        def prefill(params, cache, tokens, pos_offset, pad_mask):
            logits, cache = apply_model(params, tokens, cfg, acfg=acfg,
                                        cache=cache, cache_pos=0,
                                        pos_offset=pos_offset,
                                        pad_mask=pad_mask, last_only=True)
            return logits[:, -1], cache

        def insert(cache, row, slot):
            return jax.tree.map(
                lambda c, r: jax.lax.dynamic_update_slice_in_dim(
                    c, r.astype(c.dtype), slot, axis=1), cache, row)

        def decode(params, cache, tokens, pos, pos_offset, pad_mask):
            logits, cache = apply_model(params, tokens, cfg, acfg=acfg,
                                        cache=cache, cache_pos=pos,
                                        decode=True, pos_offset=pos_offset,
                                        pad_mask=pad_mask)
            return logits[:, -1], cache

        self._prefill = jax.jit(prefill)
        # no donation on insert: a fresh init_cache aliases its k/v leaves
        # (the same zeros array twice), which donation rejects
        self._insert = jax.jit(insert)
        self._decode = jax.jit(decode, donate_argnums=(1,))

    def _admit(self, req: Request, slot: int, cache):
        """Prefill one request and insert its cache row at ``slot``.
        Returns (cache, first_token, next_pos, pad_off, budget)."""
        plen = len(req.prompt)
        bucket = min(_bucket(plen), self.max_seq)
        assert plen <= bucket, (plen, self.max_seq)  # run() rejects overlong
        off = bucket - plen
        toks = np.zeros((1, bucket), np.int32)
        toks[0, off:] = req.prompt
        valid = np.zeros((1, self.max_seq), bool)
        valid[0, off:] = True
        row_cache = init_cache(self.cfg, 1, self.max_seq)
        with self._mesh_scope():
            logits, row_cache = self._prefill(
                self.params, row_cache, jnp.asarray(toks),
                jnp.asarray([off], jnp.int32), jnp.asarray(valid))
            cache = self._insert(cache, row_cache,
                                 jnp.asarray(slot, jnp.int32))
        self.stats["prefills"] += 1
        tok = int(np.asarray(jnp.argmax(logits[0])))
        budget = max(0, min(req.max_new_tokens, self.max_seq - bucket))
        return cache, tok, bucket, off, budget

    def run(self, requests: list[Request], arrivals=None,
            on_token: Optional[Callable[[int, int], None]] = None
            ) -> list[Request]:
        reqs = list(requests)
        n = len(reqs)
        arr = (np.zeros(n) if arrivals is None
               else np.asarray(arrivals, np.float64))
        assert len(arr) == n
        order = sorted(range(n), key=lambda j: (arr[j], j))
        qi = 0
        slots = self.slots
        active = np.zeros(slots, bool)
        pos = np.zeros(slots, np.int32)
        offs = np.zeros(slots, np.int32)
        valid = np.zeros((slots, self.max_seq), bool)
        cur = np.zeros(slots, np.int32)
        n_out = np.zeros(slots, np.int64)
        budget = np.zeros(slots, np.int64)
        ridx = np.full(slots, -1, np.int64)
        outs: list[Optional[np.ndarray]] = [None] * slots
        cache = init_cache(self.cfg, slots, self.max_seq)
        self.stats = {"prefills": 0, "decode_steps": 0, "tokens": 0,
                      "occupancy_sum": 0, "rejected": 0}
        step = 0.0  # decode-step clock
        done = 0
        while done < n:
            # admit queued arrivals into free slots (one prefill each)
            while qi < len(order) and arr[order[qi]] <= step:
                free = np.flatnonzero(~active)
                if not free.size:
                    break
                i, j = int(free[0]), order[qi]
                qi += 1
                if len(reqs[j].prompt) > self.max_seq:
                    # over-length prompt: reject at admission (the bucketed
                    # prefill would otherwise trip its plen <= bucket
                    # invariant), report via stats, keep serving
                    reqs[j].out = np.zeros(0, np.int32)
                    self.stats["rejected"] += 1
                    done += 1
                    continue
                cache, tok, p0, off, bud = self._admit(reqs[j], i, cache)
                if bud <= 0:       # prompt fills max_seq: nothing to emit
                    reqs[j].out = np.zeros(0, np.int32)
                    done += 1
                    continue
                active[i] = True
                pos[i], offs[i], cur[i] = p0, off, tok
                valid[i] = False
                valid[i, off:] = True
                n_out[i], budget[i], ridx[i] = 0, bud, j
                outs[i] = np.zeros(bud, np.int32)
            if not active.any():
                if qi >= len(order):
                    break
                step = max(step, float(arr[order[qi]]))  # idle: jump clock
                continue
            # emit the token produced by the previous model call; evict
            # slots that hit their per-request budget the same step
            for i in np.flatnonzero(active):
                outs[i][n_out[i]] = cur[i]
                n_out[i] += 1
                self.stats["tokens"] += 1
                if on_token:
                    on_token(int(ridx[i]), int(cur[i]))
                if n_out[i] >= budget[i]:
                    reqs[ridx[i]].out = outs[i][:n_out[i]].copy()
                    active[i] = False
                    done += 1
            if not active.any():
                continue
            with self._mesh_scope():
                logits, cache = self._decode(
                    self.params, cache, jnp.asarray(cur)[:, None],
                    jnp.asarray(pos), jnp.asarray(offs), jnp.asarray(valid))
            nxt = np.asarray(jnp.argmax(logits, -1))
            live = np.flatnonzero(active)
            cur[live] = nxt[live]
            pos[live] += 1
            self.stats["decode_steps"] += 1
            self.stats["occupancy_sum"] += int(live.size)
            step += 1.0
        self.stats["occupancy"] = (
            self.stats["occupancy_sum"] / max(1, self.stats["decode_steps"]))
        return requests


def kv_block_bytes(cfg: ModelConfig, block_size: int, dtype=None) -> int:
    """HBM bytes one physical KV block costs across the whole model: K and V,
    every KV head, every attention layer (a page-table entry maps the same
    block id in every layer's pool — blocks are allocated per slot, not per
    layer)."""
    dtype = dtype or cfg.param_dtype
    n_attn = sum(1 for k in cfg.pattern if k.startswith("attn")) * cfg.n_groups
    return (2 * n_attn * cfg.n_kv_heads * block_size * cfg.head_dim
            * jnp.dtype(dtype).itemsize)


class BlockAllocator:
    """Refcounted free-list over ``n_blocks`` physical KV blocks.

    Block 0 is the *null* block: page tables default to it for unallocated
    logical blocks, it is never handed out and never written, so it stays
    all-zeros (non-causal/window gathers through it see exactly what a
    contiguous cache holds past its fill). Block 1 is the *scratch* block
    that released slots' page tables point at; decode never writes it,
    since idle rows replay a live row. Shared prefix blocks carry one ref
    per sharer plus one for the prefix cache itself; a block returns to the
    free list when its refcount drains to zero.
    """

    NULL = 0
    SCRATCH = 1
    RESERVED = 2

    def __init__(self, n_blocks: int):
        assert n_blocks > self.RESERVED, n_blocks
        self.n_blocks = n_blocks
        self._free = list(range(n_blocks - 1, self.RESERVED - 1, -1))
        self._rc = np.zeros(n_blocks, np.int32)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_blocks - self.RESERVED - len(self._free)

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        blk = self._free.pop()
        self._rc[blk] = 1
        return blk

    def ref(self, blk: int) -> int:
        assert self._rc[blk] > 0, blk
        self._rc[blk] += 1
        return blk

    def release(self, blk: int) -> bool:
        """Drop one ref; returns True when the block went back on the free
        list."""
        assert self._rc[blk] > 0, blk
        self._rc[blk] -= 1
        if self._rc[blk] == 0:
            self._free.append(blk)
            return True
        return False

    def refcount(self, blk: int) -> int:
        return int(self._rc[blk])


class PagedContinuousServeEngine:
    """Continuous batching over a block-paged KV cache with prefix reuse.

    The scheduler is :class:`ContinuousServeEngine`'s (per-slot cache
    positions, shared batched decode step, decode-step clock) but the cache
    is a global pool of ``block_size``-token physical blocks sized by an
    HBM budget instead of per-slot contiguous rows:

    * **Prefill** runs in block-aligned chunks (batch-1): every full
      ``block_size`` chunk is one jitted call writing exactly one pool
      block; the final partial chunk pads to a power-of-two bucket (its
      trailing pad KV lands in the tail block but is strictly
      causal-future of every real query, and each slot's decode overwrites
      one pad position per step — so it is masked ``LUT[0, .]`` mass at
      most, and deterministic, which the bitwise prefix-hit contract
      relies on). No left-padding exists, so no ``pos_offset``/``pad_mask``
      plumbing.
    * **Prefix cache**: full prompt blocks are keyed by a chained hash of
      their token contents; an admission walks the chain and *reuses* every
      leading hit (refcounted — no copy, no recompute), then replays only
      the chunks past the last hit. Replayed KV is bitwise what the cold
      run wrote (same jitted chunk calls on the same values), so a warm
      admission is bit-identical to a cold one from the first replayed
      chunk onward. A *full-prompt* entry additionally snapshots the tail
      block and the first sampled token: an exact repeat admits with zero
      prefill compute, copy-on-write duplicating the tail block before
      decode writes into it.
    * **Memory pressure**: a decode step or admission that cannot get a
      block first evicts LRU prefix-cache entries, then preempts the
      youngest running request — its emitted tokens are kept and it
      re-enters the queue with ``prompt + emitted`` (greedy decode is
      deterministic, so the continuation is the continuation), usually
      landing back on its own still-cached prefix blocks.

    ``stats`` adds ``prefill_chunks``, ``prefix_hit_blocks``,
    ``prefix_lookup_blocks``, ``full_prompt_hits``, ``cache_evictions``,
    ``preemptions``, ``rejected``, ``block_util`` (mean fraction of
    poolblocks in use per decode step) and ``peak_blocks``.
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 max_seq: int = 512, block_size: int = 16, acfg=None,
                 mesh=None, hbm_budget: Optional[int] = None,
                 prefix_cache: bool = True):
        assert max_seq % block_size == 0, (max_seq, block_size)
        # power-of-two >= the bucket floor: the tail chunk's pow2 bucket
        # must never overflow its single block
        assert block_size >= 8 and block_size & (block_size - 1) == 0, \
            block_size
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        self.block_size = block_size
        self.acfg = acfg
        self.prefix_cache = prefix_cache
        self.n_logical = max_seq // block_size
        bbytes = kv_block_bytes(cfg, block_size)
        if hbm_budget is None:
            # default budget: what the contiguous engine would pin for the
            # same (slots, max_seq) — paged then wins by packing more rows
            # into the same bytes, not by quietly getting more memory
            hbm_budget = slots * self.n_logical * bbytes
        self.hbm_budget = hbm_budget
        self.n_blocks = max(BlockAllocator.RESERVED + self.n_logical,
                            hbm_budget // bbytes)
        self.stats: dict = {}
        if mesh is None:
            self._mesh_scope = contextlib.nullcontext
        elif isinstance(mesh, MeshContext):
            self._mesh_scope = lambda: use_mesh_context(mesh)
        else:
            self._mesh_scope = lambda: use_mesh(mesh)

        def prefill_chunk(params, cache, tokens, pos, pt):
            # full-block chunk: KV side effects only, logits discarded
            logits, cache = apply_model(params, tokens, cfg, acfg=acfg,
                                        cache=cache, cache_pos=pos,
                                        last_only=True, page_table=pt)
            return logits[:, -1], cache

        def prefill_tail(params, cache, tokens, pos, pt):
            logits, cache = apply_model(params, tokens, cfg, acfg=acfg,
                                        cache=cache, cache_pos=pos,
                                        page_table=pt)
            return logits, cache

        def decode(params, cache, tokens, pos, pt):
            logits, cache = apply_model(params, tokens, cfg, acfg=acfg,
                                        cache=cache, cache_pos=pos,
                                        decode=True, page_table=pt)
            return logits[:, -1], cache

        def copy_block(cache, src, dst):
            # one physical block, every layer's K and V pool (axis 2 of the
            # group-stacked (g, Hkv, P, bk, hd) leaves)
            return jax.tree.map(
                lambda pool: jax.lax.dynamic_update_index_in_dim(
                    pool, jax.lax.dynamic_index_in_dim(
                        pool, src, axis=2, keepdims=False), dst, axis=2),
                cache)

        self._prefill_chunk = jax.jit(prefill_chunk, donate_argnums=(1,))
        self._prefill_tail = jax.jit(prefill_tail, donate_argnums=(1,))
        self._decode = jax.jit(decode, donate_argnums=(1,))
        self._copy_block = jax.jit(copy_block, donate_argnums=(0,))

    # -- prefix cache -------------------------------------------------------

    @staticmethod
    def _chain_hashes(prompt: np.ndarray, n: int, bk: int) -> list[str]:
        """Chained content hashes of the first ``n`` full blocks: block i's
        key commits to every token before it, so equal keys mean equal
        prefixes (not merely equal blocks)."""
        hs, h = [], "root"
        for c in range(n):
            h = hashlib.sha1(
                (h + "|" + prompt[c * bk:(c + 1) * bk].tobytes().hex())
                .encode()).hexdigest()
            hs.append(h)
        return hs

    def _evict_lru_entry(self) -> bool:
        """Drop the least-recently-used prefix-cache entry (either kind),
        releasing its block refs. Returns False when both caches are empty."""
        cands = [(use, "blk", k) for k, (_, use) in self._prefix.items()]
        cands += [(use, "full", k)
                  for k, (_, _, _, use) in self._full.items()]
        if not cands:
            return False
        _, kind, key = min(cands)
        if kind == "blk":
            phys, _ = self._prefix.pop(key)
            self._alloc_release(phys)
        else:
            shared, tail, _, _ = self._full.pop(key)
            for phys in shared:
                self._alloc_release(phys)
            if tail is not None:
                self._alloc_release(tail)
        self.stats["cache_evictions"] += 1
        return True

    def _alloc_release(self, blk: int) -> None:
        self.alloc.release(blk)

    def _get_block(self) -> Optional[int]:
        """Allocate a block, evicting LRU prefix-cache entries under
        pressure; None when the pool is truly exhausted."""
        while True:
            blk = self.alloc.alloc()
            if blk is not None:
                return blk
            if not self._evict_lru_entry():
                return None

    # -- admission ----------------------------------------------------------

    def _admit(self, req: Request, slot: int, cache, resume: np.ndarray):
        """Chunked block-aligned prefill of one request into ``slot``,
        reusing cached prefix blocks. Returns (cache, first_token, plen,
        budget) or (cache, None, 0, 0) when the pool cannot host the
        prompt right now (caller requeues)."""
        bk = self.block_size
        prompt = np.concatenate([np.asarray(req.prompt, np.int32), resume])
        plen = len(prompt)
        n_full = plen // bk
        t_real = plen - n_full * bk
        # the last chunk — partial, or the last full block when the prompt
        # is block-aligned — is always replayed privately: it produces the
        # admission's logits and is where decode will write
        n_shared = n_full - (1 if t_real == 0 and n_full > 0 else 0)
        tail_lo = n_shared * bk
        tl = plen - tail_lo                     # in (0, bk]
        hashes = self._chain_hashes(prompt, n_shared, bk)
        full_key = ((hashes[-1] if n_shared else "root")
                    + "|" + prompt[tail_lo:].tobytes().hex())
        table = self._tables[slot]
        table[:] = BlockAllocator.NULL
        taken: list[int] = []                   # refs to roll back on abort

        def abort():
            for phys in taken:
                self._alloc_release(phys)
            table[:] = BlockAllocator.SCRATCH
            return cache, None, 0, 0

        self._lru += 1
        full_ent = self._full.get(full_key) if self.prefix_cache else None
        if full_ent is not None:
            shared, tail_snap, first_tok, _ = full_ent
            self._full[full_key] = (shared, tail_snap, first_tok, self._lru)
            for c, phys in enumerate(shared):
                table[c] = self.alloc.ref(phys)
                taken.append(phys)
            # copy-on-write: decode writes into the tail block, so the
            # cached snapshot is duplicated into a private block first
            dst = self._get_block()
            if dst is None:
                return abort()
            taken.append(dst)
            table[n_shared] = dst
            with self._mesh_scope():
                cache = self._copy_block(cache, jnp.asarray(tail_snap),
                                         jnp.asarray(dst))
            self.stats["full_prompt_hits"] += 1
            self.stats["prefix_hit_blocks"] += n_shared + 1
            self.stats["prefix_lookup_blocks"] += n_shared + 1
            tok = first_tok
        else:
            m = 0
            while self.prefix_cache and m < n_shared \
                    and hashes[m] in self._prefix:
                phys, _ = self._prefix[hashes[m]]
                self._prefix[hashes[m]] = (phys, self._lru)
                table[m] = self.alloc.ref(phys)
                taken.append(phys)
                m += 1
            self.stats["prefix_hit_blocks"] += m
            if self.prefix_cache:
                self.stats["prefix_lookup_blocks"] += n_shared
            for c in range(m, n_shared + 1):
                blk = self._get_block()
                if blk is None:
                    return abort()
                taken.append(blk)
                table[c] = blk
            pt = jnp.asarray(table[None])
            with self._mesh_scope():
                for c in range(m, n_shared):
                    toks = jnp.asarray(prompt[None, c * bk:(c + 1) * bk])
                    _, cache = self._prefill_chunk(
                        self.params, cache, toks,
                        jnp.asarray(c * bk, jnp.int32), pt)
                    self.stats["prefill_chunks"] += 1
                tb = _bucket(tl)
                padded = np.zeros((1, tb), np.int32)
                padded[0, :tl] = prompt[tail_lo:]
                logits, cache = self._prefill_tail(
                    self.params, cache, jnp.asarray(padded),
                    jnp.asarray(tail_lo, jnp.int32), pt)
                self.stats["prefill_chunks"] += 1
            self.stats["prefills"] += 1
            tok = int(np.asarray(jnp.argmax(logits[0, tl - 1])))
            if self.prefix_cache:
                # publish the freshly computed full blocks, and snapshot
                # (tail block, first token) for exact-repeat admissions
                for c in range(m, n_shared):
                    self._prefix[hashes[c]] = (self.alloc.ref(table[c]),
                                               self._lru)
                if full_key not in self._full:
                    snap = self.alloc.alloc()   # best effort: no eviction
                    if snap is not None:
                        with self._mesh_scope():
                            cache = self._copy_block(
                                cache, jnp.asarray(int(table[n_shared])),
                                jnp.asarray(snap))
                        shared = tuple(self.alloc.ref(int(table[c]))
                                       for c in range(n_shared))
                        self._full[full_key] = (shared, snap, tok, self._lru)
        budget = max(0, min(req.max_new_tokens - len(resume),
                            self.max_seq - plen))
        return cache, tok, plen, budget

    def _release_slot(self, slot: int) -> None:
        table = self._tables[slot]
        for phys in table[table >= BlockAllocator.RESERVED]:
            self._alloc_release(int(phys))
        table[:] = BlockAllocator.SCRATCH

    # -- main loop ----------------------------------------------------------

    def run(self, requests: list[Request], arrivals=None,
            on_token: Optional[Callable[[int, int], None]] = None
            ) -> list[Request]:
        reqs = list(requests)
        n = len(reqs)
        arr = (np.zeros(n) if arrivals is None
               else np.asarray(arrivals, np.float64))
        assert len(arr) == n
        order = sorted(range(n), key=lambda j: (arr[j], j))
        qi = 0
        ready: list[int] = []                  # admission queue (indices)
        resume: dict[int, np.ndarray] = {}     # preempted: emitted-so-far
        slots = self.slots
        active = np.zeros(slots, bool)
        pos = np.zeros(slots, np.int32)
        cur = np.zeros(slots, np.int32)
        n_out = np.zeros(slots, np.int64)
        budget = np.zeros(slots, np.int64)
        ridx = np.full(slots, -1, np.int64)
        admit_seq = np.zeros(slots, np.int64)  # preemption picks the max
        outs: list[Optional[np.ndarray]] = [None] * slots
        self.alloc = BlockAllocator(self.n_blocks)
        self._tables = np.full((slots, self.n_logical),
                               BlockAllocator.SCRATCH, np.int32)
        self._prefix: dict[str, tuple[int, int]] = {}
        self._full: dict[str, tuple[tuple, Optional[int], int, int]] = {}
        self._lru = 0
        cache = init_paged_cache(self.cfg, self.n_blocks, self.block_size)
        self.stats = {"prefills": 0, "prefill_chunks": 0, "decode_steps": 0,
                      "tokens": 0, "occupancy_sum": 0, "rejected": 0,
                      "prefix_hit_blocks": 0, "prefix_lookup_blocks": 0,
                      "full_prompt_hits": 0, "cache_evictions": 0,
                      "preemptions": 0, "block_util_sum": 0.0,
                      "peak_blocks": 0}
        usable = self.n_blocks - BlockAllocator.RESERVED
        step = 0.0
        done = 0
        seq = 0

        def preempt_youngest() -> bool:
            live = np.flatnonzero(active)
            if not live.size:
                return False
            i = int(live[np.argmax(admit_seq[live])])
            j = int(ridx[i])
            resume[j] = np.asarray(outs[i][:n_out[i]], np.int32).copy()
            self._release_slot(i)
            active[i] = False
            pos[i] = 0
            ready.insert(0, j)
            self.stats["preemptions"] += 1
            return True

        while done < n:
            while qi < len(order) and arr[order[qi]] <= step:
                ready.append(order[qi])
                qi += 1
            # admit from the queue into free slots (chunked prefill each)
            while ready:
                free = np.flatnonzero(~active)
                if not free.size:
                    break
                i, j = int(free[0]), ready[0]
                res = resume.get(j, np.zeros(0, np.int32))
                plen_total = len(reqs[j].prompt) + len(res)
                if plen_total > self.max_seq:
                    # over-length (or preempted past the horizon): reject /
                    # finish with what was already emitted
                    ready.pop(0)
                    reqs[j].out = res
                    if not res.size:
                        self.stats["rejected"] += 1
                    resume.pop(j, None)
                    done += 1
                    continue
                cache, tok, p0, bud = self._admit(reqs[j], i, cache, res)
                if tok is None:
                    # pool exhausted: leave at queue head, back-pressure
                    break
                ready.pop(0)
                if bud <= 0:
                    reqs[j].out = res
                    resume.pop(j, None)
                    self._release_slot(i)
                    done += 1
                    continue
                seq += 1
                active[i] = True
                pos[i], cur[i] = p0, tok
                n_out[i], budget[i], ridx[i] = 0, bud, j
                admit_seq[i] = seq
                base = res
                outs[i] = np.concatenate(
                    [base, np.zeros(bud, np.int32)])
                n_out[i] = len(base)
                budget[i] = len(base) + bud
            if not active.any():
                if not ready and qi >= len(order):
                    break
                if not ready:
                    step = max(step, float(arr[order[qi]]))
                    continue
                raise RuntimeError(
                    f"KV pool ({usable} blocks) cannot host request "
                    f"{ready[0]} even with every slot idle")
            # emit the token from the previous model call; free finished
            for i in np.flatnonzero(active):
                outs[i][n_out[i]] = cur[i]
                n_out[i] += 1
                self.stats["tokens"] += 1
                if on_token:
                    on_token(int(ridx[i]), int(cur[i]))
                if n_out[i] >= budget[i]:
                    reqs[ridx[i]].out = outs[i][:n_out[i]].copy()
                    resume.pop(int(ridx[i]), None)
                    self._release_slot(i)
                    active[i] = False
                    done += 1
            if not active.any():
                continue
            # every live row needs its write-target block mapped before the
            # decode step touches position pos[i]
            for i in np.flatnonzero(active):
                bi = int(pos[i]) // self.block_size
                while self._tables[i, bi] < BlockAllocator.RESERVED:
                    blk = self._get_block()
                    if blk is not None:
                        self._tables[i, bi] = blk
                        break
                    if not preempt_youngest():
                        raise RuntimeError("KV pool exhausted mid-decode "
                                           "with nothing left to preempt")
                    if not active[i]:
                        break               # preempted ourselves
            live = np.flatnonzero(active)
            if not live.size:
                continue
            # idle rows replay the first live row (its token, position and
            # page table, so their KV writes repeat its own): the per-tensor
            # activation and K/V scales of the step then depend on the live
            # requests alone, which keeps a warm admission bitwise equal to
            # a cold one whatever the idle rows last held
            src = np.where(active, np.arange(slots), live[0])
            with self._mesh_scope():
                logits, cache = self._decode(
                    self.params, cache, jnp.asarray(cur[src])[:, None],
                    jnp.asarray(pos[src]), jnp.asarray(self._tables[src]))
            nxt = np.asarray(jnp.argmax(logits, -1))
            cur[live] = nxt[live]
            pos[live] += 1
            self.stats["decode_steps"] += 1
            self.stats["occupancy_sum"] += int(live.size)
            self.stats["block_util_sum"] += self.alloc.n_used / usable
            self.stats["peak_blocks"] = max(self.stats["peak_blocks"],
                                            self.alloc.n_used)
            step += 1.0
        self.stats["occupancy"] = (
            self.stats["occupancy_sum"] / max(1, self.stats["decode_steps"]))
        self.stats["block_util"] = (
            self.stats["block_util_sum"] / max(1, self.stats["decode_steps"]))
        self.stats["prefix_hit_rate"] = (
            self.stats["prefix_hit_blocks"]
            / max(1, self.stats["prefix_lookup_blocks"]))
        return requests


class VisionServeEngine:
    """Batched image-inference serving: fixed-size waves through one jitted
    forward, mesh-aware like :class:`ServeEngine`.

    ``forward_fn(params, images, acfg) -> logits`` is any vision model
    forward (``repro.models.vision.cnn_forward`` / ``resnet_forward`` / ...);
    every conv inside it resolves a :func:`~repro.core.acu.conv_plan`, so
    with a LUT-Pallas ``acfg`` the whole stack rides the fused
    patch-streaming conv kernels — including ImageNet-scale (224^2) inputs,
    which since PR 4 resolve to the spatially-tiled kernel instead of
    reporting the eager-im2col VMEM fallback (``plan_report`` shows the
    chosen banding) — and with ``mesh=...`` the waves run under the
    ``acu_conv`` partition (batch x output-row bands over
    ``("pod", "data")``, output channels over ``("model",)``) — bit-for-bit
    the single-device logits.
    """

    def __init__(self, params, forward_fn: Callable, *, slots: int = 8,
                 acfg=None, mesh=None):
        self.params = params
        self.slots = slots
        if mesh is None:
            self._mesh_scope = contextlib.nullcontext
        elif isinstance(mesh, MeshContext):
            self._mesh_scope = lambda: use_mesh_context(mesh)
        else:
            self._mesh_scope = lambda: use_mesh(mesh)
        self._infer = jax.jit(lambda p, imgs: forward_fn(p, imgs, acfg))

    def plan_report(self, image_shape, w_shape, acfg, **geom) -> dict:
        """The conv route one layer takes under this engine's mesh scope
        (see :func:`repro.core.approx_ops.conv_plan_report`)."""
        from repro.core.approx_ops import conv_plan_report
        with self._mesh_scope():
            return conv_plan_report(image_shape, w_shape, acfg, **geom)

    def run(self, images: np.ndarray) -> np.ndarray:
        """images: (B, C, H, W) -> logits (B, n_classes), served in waves of
        ``slots`` (the last wave zero-padded and sliced)."""
        b = images.shape[0]
        outs = []
        for i in range(0, b, self.slots):
            wave = np.asarray(images[i:i + self.slots], np.float32)
            pad = self.slots - wave.shape[0]
            if pad:
                wave = np.concatenate(
                    [wave, np.zeros((pad, *wave.shape[1:]), wave.dtype)])
            with self._mesh_scope():
                logits = self._infer(self.params, jnp.asarray(wave))
            outs.append(np.asarray(logits)[:self.slots - pad])
        return np.concatenate(outs, axis=0)
