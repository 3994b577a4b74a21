"""Mesh-aware execution of ACU GEMM plans (the second level of dispatch).

``core/acu.py`` resolves *what* kernel runs (mode x fused); this module
resolves *where*: with an active :class:`~repro.parallel.sharding.MeshContext`
every plan is wrapped in a ``shard_map`` that

* replicates the (2^b, 2^b) product table (<= 256 KiB) to every device,
* shards activation/output rows over the ``acu_rows`` axes (``("pod",
  "data")`` by default), weight/output columns over ``acu_cols``
  (``("model",)``),
* optionally shards the contraction dim over ``acu_k`` and psum-reduces the
  int32 partial accumulators *before* dequant,
* pads M/N/K up to the axis products and slices the result back — padding
  rows/columns only produce discarded outputs, while the K shard-padding
  contributes ``M[0, 0]`` per padded k and is corrected **exactly once
  globally** (after the psum), not once per shard.

Everything stays bit-exact against the single-device kernels: each local
kernel sees the full contraction (or an exact K slice whose int32 partials
add associatively), so the int accumulators — and hence the dequantized
floats — are identical element-for-element.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .planner import (GemmPartition, acu_attn_partition, acu_conv_partition,
                      acu_gemm_partition, acu_grouped_partition)
from .sharding import MeshContext

Array = jnp.ndarray


def resolve_partition(ctx: MeshContext, *, float_accum: bool = False
                      ) -> Optional[GemmPartition]:
    """Partition for the active mesh, or None when every axis is trivial
    (1x1 host mesh: the wrap would be a no-op, so the plan stays local)."""
    part, _ = acu_gemm_partition(ctx, float_accum=float_accum)
    return part if part.total > 1 else None


def resolve_conv_partition(ctx: MeshContext, *, float_accum: bool = False
                           ) -> Optional[GemmPartition]:
    """The ``acu_conv`` partition for the active mesh (rows = batch x
    output pixels, cols = output channels, k = input channels), or None when
    every axis is trivial."""
    part, _ = acu_conv_partition(ctx, float_accum=float_accum)
    return part if part.total > 1 else None


def resolve_attn_partition(ctx: MeshContext, *, hq: int, hkv: int
                           ) -> Optional[GemmPartition]:
    """The ``acu_attn`` partition for the active mesh (rows = batch, cols =
    KV heads with whole GQA groups per shard), or None when every axis is
    trivial."""
    part, _ = acu_attn_partition(ctx, hq=hq, hkv=hkv)
    return part if part.total > 1 else None


def resolve_grouped_partition(ctx: MeshContext, *, n_experts: int,
                              n_blocks: int) -> Optional[GemmPartition]:
    """The ``acu_grouped`` partition for the active mesh (rows = dispatch
    blocks, cols = whole experts per shard, k = opt-in contraction), or None
    when every axis is trivial."""
    part, _ = acu_grouped_partition(ctx, n_experts=n_experts,
                                    n_blocks=n_blocks)
    return part if part.total > 1 else None


def _pad2(x: Array, pr: int, pc: int) -> Array:
    return jnp.pad(x, ((0, pr), (0, pc))) if (pr or pc) else x


def wrap_attn(attn_call: Callable[..., Array], ctx: MeshContext,
              part: GemmPartition, *, hq: int, hkv: int
              ) -> Callable[..., Array]:
    """Shard an approximate attention plan
    ``fn(q, k, v, qs, ks, vs, rowinfo) -> (B, Hq, Sq, D) f32``.

    ``q``: (B, Hq, Sq, D) float; ``k``/``v``: (B, Hkv, Sk, D);
    ``rowinfo``: (B, 3) int32 ``[q_base, kv_start, kv_len]`` rows (one per
    batch row — heads of a sequence share its cache geometry). Batch rows
    shard over ``part.rows``, KV heads over ``part.cols`` — each shard gets
    whole GQA groups (``rep`` query heads per KV head), runs the full fused
    kernel on its (B_loc * Hq_loc) fold, and there are no collectives: the
    kernel grid is embarrassingly parallel over (batch*head, q_block), so
    the wrap is bit-exact by construction. Scales are computed by the
    caller on the FULL tensors and replicated — every shard sees identical
    quantization. Padded batch rows carry rowinfo ``[0, 0, 0]``: every key
    masked, finite garbage output, sliced off here.
    """
    mesh = ctx.mesh
    assert hq % hkv == 0 and hkv % part.n_cols == 0, (hq, hkv, part.n_cols)

    def fn(q: Array, k: Array, v: Array, qs, ks, vs, rowinfo: Array) -> Array:
        b, _, sq, d = q.shape
        pb = (-b) % part.n_rows
        if pb:
            q = jnp.pad(q, ((0, pb), (0, 0), (0, 0), (0, 0)))
            k = jnp.pad(k, ((0, pb), (0, 0), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, pb), (0, 0), (0, 0), (0, 0)))
            rowinfo = jnp.pad(rowinfo, ((0, pb), (0, 0)))
        qs_a = jnp.asarray(qs, jnp.float32).reshape(1)
        ks_a = jnp.asarray(ks, jnp.float32).reshape(1)
        vs_a = jnp.asarray(vs, jnp.float32).reshape(1)

        rows = part._dim(part.rows)
        cols = part._dim(part.cols)

        def local(q_blk, k_blk, v_blk, qs_b, ks_b, vs_b, info_blk):
            bl, hql = q_blk.shape[0], q_blk.shape[1]
            info = jnp.repeat(info_blk, hql, axis=0)     # (bl*hql, 3)
            out = attn_call(
                q_blk.reshape(bl * hql, *q_blk.shape[2:]),
                k_blk.reshape(bl * k_blk.shape[1], *k_blk.shape[2:]),
                v_blk.reshape(bl * v_blk.shape[1], *v_blk.shape[2:]),
                qs_b, ks_b, vs_b, info)
            return out.reshape(bl, hql, *out.shape[1:])

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(rows, cols, None, None), P(rows, cols, None, None),
                      P(rows, cols, None, None), P(None), P(None), P(None),
                      P(rows, None)),
            out_specs=P(rows, cols, None, None), check_vma=False,
        )(q, k, v, qs_a, ks_a, vs_a, rowinfo)
        return out[:b]

    return fn


def wrap_attn_paged(attn_call: Callable[..., Array], ctx: MeshContext,
                    part: GemmPartition, *, hq: int, hkv: int
                    ) -> Callable[..., Array]:
    """Shard a paged approximate attention plan
    ``fn(q, k_pool, v_pool, qs, ks, vs, rowinfo, page_table) ->
    (B, Hq, Sq, D) f32``.

    Same geometry as :func:`wrap_attn` — batch rows over ``part.rows``,
    KV heads over ``part.cols`` in whole GQA groups, no collectives — with
    the paged twists: the ``(Hkv, P, bk, D)`` physical pools shard over
    ``part.cols`` on their head axis and REPLICATE over the row axes (every
    batch shard reads the same pool), while the ``(B, n_logical)`` page
    table shards with the batch rows like ``rowinfo`` and replicates over
    the head axis — the table is head-independent by construction (one
    pool row per KV head, same block ids). The local fold keeps the global
    ``rep``: with ``hql = hq/n_cols`` local query heads and
    ``hkv_loc = hkv/n_cols`` local pool rows, the kernel's
    ``(b // rep) % hkv_loc`` lands each local query head on its own KV
    head for every batch index. Padded batch rows carry rowinfo
    ``[0, 0, 0]`` and an all-zeros page table (physical block 0 — the
    engine's permanently-zero null block): every key masked, finite
    garbage, sliced off here.
    """
    mesh = ctx.mesh
    assert hq % hkv == 0 and hkv % part.n_cols == 0, (hq, hkv, part.n_cols)

    def fn(q: Array, k_pool: Array, v_pool: Array, qs, ks, vs,
           rowinfo: Array, page_table: Array) -> Array:
        b = q.shape[0]
        pb = (-b) % part.n_rows
        if pb:
            q = jnp.pad(q, ((0, pb), (0, 0), (0, 0), (0, 0)))
            rowinfo = jnp.pad(rowinfo, ((0, pb), (0, 0)))
            page_table = jnp.pad(page_table, ((0, pb), (0, 0)))
        qs_a = jnp.asarray(qs, jnp.float32).reshape(1)
        ks_a = jnp.asarray(ks, jnp.float32).reshape(1)
        vs_a = jnp.asarray(vs, jnp.float32).reshape(1)

        rows = part._dim(part.rows)
        cols = part._dim(part.cols)

        def local(q_blk, kp_blk, vp_blk, qs_b, ks_b, vs_b, info_blk, pt_blk):
            bl, hql = q_blk.shape[0], q_blk.shape[1]
            info = jnp.repeat(info_blk, hql, axis=0)     # (bl*hql, 3)
            pt = jnp.repeat(pt_blk, hql, axis=0)         # (bl*hql, n_log)
            out = attn_call(
                q_blk.reshape(bl * hql, *q_blk.shape[2:]),
                kp_blk, vp_blk, qs_b, ks_b, vs_b, info, pt)
            return out.reshape(bl, hql, *out.shape[1:])

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(rows, cols, None, None),
                      P(cols, None, None, None), P(cols, None, None, None),
                      P(None), P(None), P(None),
                      P(rows, None), P(rows, None)),
            out_specs=P(rows, cols, None, None), check_vma=False,
        )(q, k_pool, v_pool, qs_a, ks_a, vs_a, rowinfo, page_table)
        return out[:b]

    return fn


def wrap_unfused(base_fn: Callable[[Array, Array], Array], ctx: MeshContext,
                 part: GemmPartition, m00: int) -> Callable[[Array, Array], Array]:
    """Shard an unfused integer-operand GEMM ``fn(a, w) -> acc``.

    ``m00`` is the multiplier's product at shifted code (0, 0) — what every
    K shard-pad entry contributes to the accumulator.
    """
    mesh = ctx.mesh

    def fn(a: Array, w: Array) -> Array:
        M, K = a.shape
        N = w.shape[1]
        pm, pk, pn = (-M) % part.n_rows, (-K) % part.n_k, (-N) % part.n_cols
        a_p = _pad2(a, pm, pk)          # code 0 == shifted zero-point
        w_p = _pad2(w, pk, pn)

        def local(a_blk, w_blk):
            acc = base_fn(a_blk, w_blk)
            if part.k:
                acc = jax.lax.psum(acc, part.k)
            return acc

        out = jax.shard_map(
            local, mesh=mesh, in_specs=(part.a_spec(), part.w_spec()),
            out_specs=part.out_spec(), check_vma=False)(a_p, w_p)
        if pk and m00:
            # global K shard-padding correction: applied once, after the
            # psum — each pad entry contributed m00 to exactly one k shard
            out = out - jnp.asarray(pk * m00, out.dtype)
        return out[:M, :N]

    return fn


def wrap_fused(fused_call: Callable[..., Array],
               acc_call: Callable[..., Array], ctx: MeshContext,
               part: GemmPartition, m00: int) -> Callable[..., Array]:
    """Shard a fused quantize->LUT-GEMM->dequant plan
    ``fn(x, wq, xs, xz, ws) -> f32``.

    Without K sharding each shard runs the full fused kernel (dequant stays
    in-kernel). With K sharding the kernel emits the raw int32 accumulator
    (``acc_call``), partials psum in integer space, the global K-pad
    correction lands once, and the dequant — the same ``acc * xs * ws``
    expression the kernel uses — runs on the reduced accumulator.
    """
    mesh = ctx.mesh

    def fn(x: Array, wq: Array, xs, xz, ws) -> Array:
        M, K = x.shape
        N = wq.shape[1]
        pm, pk, pn = (-M) % part.n_rows, (-K) % part.n_k, (-N) % part.n_cols
        x_p = _pad2(x, pm, pk)          # 0.0 quantizes to the zero-point
        wq_p = _pad2(wq, pk, pn)        # shifted code 0
        ws_row = jnp.broadcast_to(
            jnp.asarray(ws, jnp.float32).reshape(1, -1), (1, N))
        ws_p = _pad2(ws_row, 0, pn)
        xs_a = jnp.asarray(xs, jnp.float32).reshape(1)
        xz_a = jnp.asarray(xz, jnp.float32).reshape(1)

        if not part.k:
            def local(x_blk, wq_blk, xs_b, xz_b, ws_blk):
                return fused_call(x_blk, wq_blk, xs_b, xz_b, ws_blk[0])
        else:
            def local(x_blk, wq_blk, xs_b, xz_b, ws_blk):
                acc = acc_call(x_blk, wq_blk, xs_b, xz_b, ws_blk[0])
                acc = jax.lax.psum(acc, part.k)
                if pk and m00:
                    acc = acc - jnp.asarray(pk * m00, acc.dtype)
                # same single combined-scale multiply as the kernel's in-VMEM
                # dequant — bit-exact vs the single-device output
                return acc.astype(jnp.float32) * (xs_b[0] * ws_blk)

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(part.a_spec(), part.w_spec(), P(None), P(None),
                      P(None, part._dim(part.cols))),
            out_specs=part.out_spec(), check_vma=False,
        )(x_p, wq_p, xs_a, xz_a, ws_p)
        return out[:M, :N]

    return fn


def wrap_fused_bwd(bwd_call: Callable[..., Array],
                   acc_call: Callable[..., Array], ctx: MeshContext,
                   part: GemmPartition, m00: int) -> Callable[..., Array]:
    """Shard a fused approximate-backward GEMM
    ``fn(a, b, sa, sb) -> f32 (M, N)``.

    Both operands are float residuals quantized *inside* the kernel with
    per-tensor symmetric scales computed by the caller on the full tensors
    (outside this wrap — every shard must see the same scale). ``part`` is a
    permuted forward partition (:func:`~repro.parallel.planner.
    bwd_gemm_partitions`), so the contraction axes here are the forward's
    rows or cols axes. Without contraction sharding each shard runs the full
    fused kernel; with it the kernel emits raw int32 partials (``acc_call``),
    they psum in integer space, the K shard-padding correction — zero pads
    quantize to code 0, contributing ``M[0, 0]`` each — lands exactly once
    after the collective, and the single combined-scale dequant runs on the
    reduced accumulator. Bit-exact vs the single-device kernel.
    """
    mesh = ctx.mesh

    def fn(a: Array, b: Array, sa, sb) -> Array:
        M, K = a.shape
        N = b.shape[1]
        pm, pk, pn = (-M) % part.n_rows, (-K) % part.n_k, (-N) % part.n_cols
        a_p = _pad2(a, pm, pk)      # 0.0 quantizes to code 0 (symmetric)
        b_p = _pad2(b, pk, pn)
        sa_a = jnp.asarray(sa, jnp.float32).reshape(1)
        sb_a = jnp.asarray(sb, jnp.float32).reshape(1)

        if not part.k:
            def local(a_blk, b_blk, sa_b, sb_b):
                return bwd_call(a_blk, b_blk, sa_b, sb_b)
        else:
            def local(a_blk, b_blk, sa_b, sb_b):
                acc = acc_call(a_blk, b_blk, sa_b, sb_b)
                acc = jax.lax.psum(acc, part.k)
                if pk and m00:
                    acc = acc - jnp.asarray(pk * m00, acc.dtype)
                # same single combined-scale multiply as the kernel's
                # in-VMEM dequant, with the scale product pinned to one f32
                # rounding: both factors are scalars here, and the jitted
                # SPMD program otherwise reassociates acc * sa * sb
                from repro.core.quantization import pin_rounding
                return acc.astype(jnp.float32) * pin_rounding(sa_b[0] * sb_b[0])

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(part.a_spec(), part.w_spec(), P(None), P(None)),
            out_specs=part.out_spec(), check_vma=False,
        )(a_p, b_p, sa_a, sb_a)
        return out[:M, :N]

    return fn


def wrap_fused_grouped(grouped_call: Callable[..., Array],
                       acc_call: Callable[..., Array], ctx: MeshContext,
                       part: GemmPartition, m00: int, *, n_experts: int
                       ) -> Callable[..., Array]:
    """Shard a fused grouped ragged GEMM plan
    ``fn(xe, wq, xs, xz, ws, counts) -> (G, C, N) f32``.

    ``xe``: (G, C, K) dispatched capacity buffers with ``G = nb * E`` groups
    laid out block-major — reshaped to (nb, E, C, K) here so dispatch blocks
    shard over ``part.rows`` and experts over ``part.cols`` (expert
    parallelism). Each shard keeps whole experts and whole dispatch blocks
    (the partition resolver drops non-dividing axes), so the local group ->
    expert mapping ``g % E_loc`` of the flattened (nb_loc * E_loc) slice is
    exactly the global mapping restricted to the shard, the LUT and the
    shared activation scale replicate, and the groupinfo counts ride with
    their groups. Without K sharding each shard runs the full fused kernel
    (dead-row masking stays in-kernel). With K sharding the kernel emits the
    masked int32 accumulator (``acc_call``), partials psum in integer space,
    the global K-pad correction lands once — which un-zeroes the dead rows,
    so the live-row mask is re-applied after the dequant. Bit-exact vs the
    single-device grouped kernel.
    """
    mesh = ctx.mesh

    def fn(xe: Array, wq: Array, xs, xz, ws, counts: Array) -> Array:
        G, C, K = xe.shape
        E, _, N = wq.shape
        assert E == n_experts and G % E == 0, (G, E, n_experts)
        nb = G // E
        assert nb % part.n_rows == 0 and E % part.n_cols == 0, (
            f"partition {part.n_rows}x{part.n_cols} does not divide "
            f"blocks={nb} experts={E} (resolver should have dropped axes)")
        pk = (-K) % part.n_k
        x4 = xe.reshape(nb, E, C, K)
        if pk:  # 0.0 quantizes to the zero-point -> shifted code 0
            x4 = jnp.pad(x4, ((0, 0), (0, 0), (0, 0), (0, pk)))
            wq = jnp.pad(wq, ((0, 0), (0, pk), (0, 0)))
        ws_e = jnp.broadcast_to(
            jnp.asarray(ws, jnp.float32).reshape(E, -1), (E, N))
        xs_a = jnp.asarray(xs, jnp.float32).reshape(1)
        xz_a = jnp.asarray(xz, jnp.float32).reshape(1)
        cnt = jnp.asarray(counts, jnp.int32).reshape(nb, E)

        rows = part._dim(part.rows)
        cols = part._dim(part.cols)
        kdim = part._dim(part.k)

        if not part.k:
            def local(x_blk, wq_blk, xs_b, xz_b, ws_blk, cnt_blk):
                nbl, el = x_blk.shape[0], x_blk.shape[1]
                out = grouped_call(
                    x_blk.reshape(nbl * el, *x_blk.shape[2:]), wq_blk,
                    xs_b, xz_b, ws_blk, cnt_blk.reshape(-1))
                return out.reshape(nbl, el, *out.shape[1:])
        else:
            def local(x_blk, wq_blk, xs_b, xz_b, ws_blk, cnt_blk):
                nbl, el = x_blk.shape[0], x_blk.shape[1]
                acc = acc_call(
                    x_blk.reshape(nbl * el, *x_blk.shape[2:]), wq_blk,
                    xs_b, xz_b, ws_blk, cnt_blk.reshape(-1))
                acc = jax.lax.psum(acc, part.k)
                if pk and m00:
                    acc = acc - jnp.asarray(pk * m00, acc.dtype)
                # same single combined-scale multiply as the kernel's in-VMEM
                # dequant; then re-mask — the uniform pad correction gave the
                # dead rows (zeroed in integer space per shard) -pk*m00
                deq = (acc.reshape(nbl, el, *acc.shape[1:]).astype(jnp.float32)
                       * (xs_b[0] * ws_blk)[None, :, None, :])
                live = (jnp.arange(deq.shape[2])[None, None, :]
                        < cnt_blk[:, :, None])
                return jnp.where(live[..., None], deq, 0.0)

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(rows, cols, None, kdim), P(cols, kdim, None),
                      P(None), P(None), P(cols, None), P(rows, cols)),
            out_specs=P(rows, cols, None, None), check_vma=False,
        )(x4, wq, xs_a, xz_a, ws_e, cnt)
        return out.reshape(G, C, N)

    return fn


def _conv_band_ways(n: int, ho: int, n_rows: int) -> int:
    """Output-row band ways for the conv rows partition: when the batch
    alone cannot fill the ``acu_conv_rows`` axes (N < n_rows with N | n_rows),
    each image's output rows split into ``n_rows // N`` halo'd bands so the
    spare devices compute spatial bands instead of padding images."""
    if n >= n_rows or n_rows % n != 0:
        return 1
    bw = n_rows // n
    return bw if ho >= bw else 1


def wrap_fused_conv(conv_call: Callable[..., Array],
                    acc_call: Callable[..., Array], ctx: MeshContext,
                    part: GemmPartition, m00: int, n_taps: int, *,
                    spec=None) -> Callable[..., Array]:
    """Shard a fused patch-streaming conv plan
    ``fn(x, wq, xs, xz, ws) -> (N, Ho, Wo, Cout) f32``.

    ``x``: (N, C, H, W) float; ``wq``: (Cout, C, kh, kw) shifted weight
    codes. The *batch x output-row-band* dim shards over ``part.rows`` (the
    output-pixel rows of the implicit im2col GEMM follow their image — and,
    when the batch alone cannot fill the rows axes, each image splits into
    halo'd output-row bands, each shard slicing its own slab inside the
    ``shard_map``, so e.g. a single 224^2 image still uses every rows-axis
    device). Output channels
    shard over ``part.cols``, and the LUT replicates — every shard runs the
    full fused kernel (whole-image or spatially tiled) on its
    (batch x band, Cout) tile, so there are no collectives and the wrap is
    bit-exact by construction: band slabs carry their own halo rows, and
    int32 tap accumulation is order-independent. With ``part.k`` the *input
    channels* split: each shard's kernel emits its raw int32 partial
    accumulator (``acc_call``), partials psum in integer space, and the
    global channel-shard-padding correction — ``pad_c * n_taps * M[0, 0]``,
    one ``M[0, 0]`` per padded channel per kernel tap — lands exactly once,
    after the collective, before the single combined-scale dequant.

    ``n_taps`` is ``kh * kw`` (each padded channel feeds every tap).
    ``spec`` is the plan's :class:`~repro.core.acu.ConvSpec`; band
    partitioning needs its static geometry and is skipped when absent.
    """
    mesh = ctx.mesh

    def fn(x: Array, wq: Array, xs, xz, ws) -> Array:
        n, c, h = x.shape[0], x.shape[1], x.shape[2]
        cout = wq.shape[0]
        band_ways = 1
        if spec is not None and part.rows:
            band_ways = _conv_band_ways(n, spec.out_spatial[0], part.n_rows)
        pk = (-c) % part.n_k
        pn = (-cout) % part.n_cols

        if band_ways > 1:
            # halo'd band sharding: conv row padding materializes here
            # (zeros), each shard dynamic-slices its own slab inside the
            # shard_map from its rows-axis index — slab extraction must not
            # go through an XLA concat feeding the shard_map (the SPMD
            # partitioner mis-reshards concat-of-slices), and on real
            # hardware this is where a halo exchange would go
            (ph0, _), (pw0, pw1) = spec.padding
            sh = spec.stride[0]
            kh = spec.w_shape[2]
            dh = spec.dilation[0]
            ho, _ = spec.out_spatial
            ho_band = -(-ho // band_ways)
            slab_rows = (ho_band - 1) * sh + (kh - 1) * dh + 1
            rows_needed = (band_ways - 1) * ho_band * sh + slab_rows
            x = jnp.pad(x, ((0, 0), (0, pk),
                            (ph0, max(0, rows_needed - h - ph0)), (0, 0)))
            x = x[:, :, :rows_needed]   # rows past the last slab: never read
            pb = 0
            call_kw = {"padding": ((0, 0), (pw0, pw1))}

            def extract(x_blk):
                r = 0
                for a in part.rows:     # linear index along the rows axes
                    r = r * mesh.shape[a] + jax.lax.axis_index(a)
                b_idx = r // band_ways
                band = r % band_ways
                return jax.lax.dynamic_slice(
                    x_blk, (b_idx, 0, band * ho_band * sh, 0),
                    (1, x_blk.shape[1], slab_rows, x_blk.shape[3]))
        else:
            pb = (-n) % part.n_rows
            if pb or pk:
                x = jnp.pad(x, ((0, pb), (0, pk), (0, 0), (0, 0)))
            call_kw = {}
            extract = lambda x_blk: x_blk

        if pn or pk:  # pad channels: shifted code 0; pad couts: discarded
            wq = jnp.pad(wq, ((0, pn), (0, pk), (0, 0), (0, 0)))
        ws_row = jnp.broadcast_to(
            jnp.asarray(ws, jnp.float32).reshape(1, -1), (1, cout))
        if pn:
            ws_row = jnp.pad(ws_row, ((0, 0), (0, pn)))
        xs_a = jnp.asarray(xs, jnp.float32).reshape(1)
        xz_a = jnp.asarray(xz, jnp.float32).reshape(1)

        rows = part._dim(part.rows)
        cols = part._dim(part.cols)
        kdim = part._dim(part.k)
        # banded: the image batch replicates over the rows axes (each shard
        # carves out its slab); otherwise the batch dim itself shards
        x_rows = None if band_ways > 1 else rows

        if not part.k:
            def local(x_blk, wq_blk, xs_b, xz_b, ws_blk):
                return conv_call(extract(x_blk), wq_blk, xs_b, xz_b,
                                 ws_blk[0], **call_kw)
        else:
            def local(x_blk, wq_blk, xs_b, xz_b, ws_blk):
                acc = acc_call(extract(x_blk), wq_blk, xs_b, xz_b,
                               ws_blk[0], **call_kw)
                acc = jax.lax.psum(acc, part.k)
                if pk and m00:
                    # global channel-shard-padding correction: each padded
                    # channel contributed m00 through every tap, to exactly
                    # one channel shard — corrected once, after the psum
                    acc = acc - jnp.asarray(pk * n_taps * m00, acc.dtype)
                # same single combined-scale multiply as the in-kernel dequant
                return acc.astype(jnp.float32) * \
                    (xs_b[0] * ws_blk).reshape(1, 1, 1, -1)

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(x_rows, kdim, None, None), P(cols, kdim, None, None),
                      P(None), P(None), P(None, cols)),
            out_specs=P(rows, None, None, cols), check_vma=False,
        )(x, wq, xs_a, xz_a, ws_row)
        if band_ways > 1:
            ho, wo = spec.out_spatial
            out = out[:, :, :, :cout]
            out = out.reshape(n, band_ways * out.shape[1], wo, cout)
            return out[:, :ho]
        return out[:n, :, :, :cout]

    return fn


def wrap_conv_bwd_w(acc_call: Callable[..., Array], ctx: MeshContext,
                    part: GemmPartition, spec) -> Callable[..., Array]:
    """Shard the banded approximate conv weight-grad
    ``fn(xf, g, sx, sg) -> (kh*kw, Cin, Cout) int32``.

    The weight-grad contracts over output pixels — the *rows* of the conv
    partition — so the batch x output-row-band dim shards over ``part.rows``
    (halo'd band slabs, same machinery as the forward's
    :func:`wrap_fused_conv`) and the per-shard int32 partials **psum over
    the rows axes**. Output channels shard over ``part.cols`` and input
    channels over ``part.k`` — both are *output* dims of gw, so they carve
    the accumulator without collectives, staying sharded exactly as the
    forward left them. There is no pad-correction term at all: padded batch
    images and dead band-slab rows carry a zero ``rmask`` (the kernel masks
    them multiplicatively, because an invalid row contributes the
    non-constant ``M[x, 0]``), and padded cin/cout only produce discarded
    accumulator slices. ``acc_call(x, g, rmask, sx, sg, padding)`` is the
    single-device banded kernel wrapper; bit-exactness is by construction —
    int32 pixel partials add associatively across shards.
    """
    mesh = ctx.mesh

    def fn(xf: Array, g: Array, sx, sg) -> Array:
        n, c, h = xf.shape[0], xf.shape[1], xf.shape[2]
        cout = g.shape[3]
        kh = spec.w_shape[2]
        ho, wo = spec.out_spatial
        band_ways = 1
        if part.rows:
            band_ways = _conv_band_ways(n, ho, part.n_rows)
        pk = (-c) % part.n_k
        pn = (-cout) % part.n_cols
        sh = spec.stride[0]
        dh = spec.dilation[0]
        (ph0, _), (pw0, pw1) = spec.padding

        if band_ways > 1:
            # conv row padding materializes here (zeros); each shard
            # dynamic-slices its halo'd slab from its rows-axis index —
            # never an XLA concat feeding the shard_map
            ho_band = -(-ho // band_ways)
            slab_rows = (ho_band - 1) * sh + (kh - 1) * dh + 1
            rows_needed = (band_ways - 1) * ho_band * sh + slab_rows
            xf = jnp.pad(xf, ((0, 0), (0, pk),
                              (ph0, max(0, rows_needed - h - ph0)), (0, 0)))
            xf = xf[:, :, :rows_needed]
            g = jnp.pad(g, ((0, 0), (0, band_ways * ho_band - ho),
                            (0, 0), (0, pn)))
            pad_kw = {"padding": ((0, 0), (pw0, pw1))}
            x_rows = g_rows = None   # replicated; slabs carved per shard

            def extract(x_blk, g_blk, rm_blk):
                r = 0
                for a in part.rows:
                    r = r * mesh.shape[a] + jax.lax.axis_index(a)
                b_idx = r // band_ways
                band = r % band_ways
                x_sl = jax.lax.dynamic_slice(
                    x_blk, (b_idx, 0, band * ho_band * sh, 0),
                    (1, x_blk.shape[1], slab_rows, x_blk.shape[3]))
                g_sl = jax.lax.dynamic_slice(
                    g_blk, (b_idx, band * ho_band, 0, 0),
                    (1, ho_band, g_blk.shape[2], g_blk.shape[3]))
                # slab rows past Ho (last band of an uneven split) are dead
                rm = ((band * ho_band + jnp.arange(ho_band)) < ho
                      ).astype(jnp.int32).reshape(1, ho_band)
                return x_sl, g_sl, rm
        else:
            pb = (-n) % part.n_rows
            if pb or pk:
                xf = jnp.pad(xf, ((0, pb), (0, pk), (0, 0), (0, 0)))
            if pb or pn:
                g = jnp.pad(g, ((0, pb), (0, 0), (0, 0), (0, pn)))
            rmask = jnp.pad(jnp.ones((n, ho), jnp.int32),
                            ((0, pb), (0, 0)))   # padded images: dead rows
            pad_kw = {"padding": spec.padding}
            x_rows = g_rows = part._dim(part.rows)
            extract = lambda x_blk, g_blk, rm_blk: (x_blk, g_blk, rm_blk)

        sx_a = jnp.asarray(sx, jnp.float32).reshape(1)
        sg_a = jnp.asarray(sg, jnp.float32).reshape(1)
        cols = part._dim(part.cols)
        kdim = part._dim(part.k)

        def local(x_blk, g_blk, rm_blk, sx_b, sg_b):
            x_sl, g_sl, rm = extract(x_blk, g_blk, rm_blk)
            acc = acc_call(x_sl, g_sl, rm, sx_b, sg_b, **pad_kw)
            if part.rows:
                # the pixel contraction: int32 partials, one per band slab
                acc = jax.lax.psum(acc, part.rows)
            return acc

        rm_arg = rmask if band_ways == 1 else \
            jnp.zeros((1, 1), jnp.int32)   # unused; built inside extract
        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(x_rows, kdim, None, None),
                      P(g_rows, None, None, cols),
                      P(g_rows, None) if band_ways == 1 else P(None, None),
                      P(None), P(None)),
            out_specs=P(None, kdim, cols), check_vma=False,
        )(xf, g, rm_arg, sx_a, sg_a)
        return out[:, :c, :cout]

    return fn


def wrap_conv_gx_gemm(acc_call: Callable[..., Array], ctx: MeshContext,
                      part: GemmPartition, m00: int) -> Callable[..., Array]:
    """Shard one per-band input-grad GEMM ``fn(g2, wfmat, sg, sw) -> int32``.

    ``g2``: (band pixels, Cout) float gradient rows; ``wfmat``: (Cout,
    C*kh*kw) float residual weights. The contraction dim is Cout — the conv
    partition's *cols* axes — so the weight operand stays sharded exactly as
    the forward left it: each cols shard runs the fused backward kernel on
    its Cout slice (``acc_call`` = ``fused_lut_bwd`` with ``emit_acc``),
    the int32 partials psum over ``part.cols``, and the Cout shard-padding
    correction — zero pads quantize to code 0, contributing ``M[0, 0]``
    each — lands exactly once, after the collective. Rows and k axes are
    idle here (the band's pixel rows and the patch-feature columns stay
    whole); they compute replicated. The caller scatters the returned
    accumulator into the integer gradient canvas and dequants once.
    """
    mesh = ctx.mesh

    def fn(g2: Array, bmat: Array, sg, sw) -> Array:
        K = g2.shape[1]
        pk = (-K) % part.n_cols
        g2_p = _pad2(g2, 0, pk)     # 0.0 quantizes to code 0 (symmetric)
        b_p = _pad2(bmat, pk, 0)
        sg_a = jnp.asarray(sg, jnp.float32).reshape(1)
        sw_a = jnp.asarray(sw, jnp.float32).reshape(1)
        cols = part._dim(part.cols)

        def local(a_blk, b_blk, sa_b, sb_b):
            acc = acc_call(a_blk, b_blk, sa_b, sb_b)
            if part.cols:
                acc = jax.lax.psum(acc, part.cols)
            return acc

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(None, cols), P(cols, None), P(None), P(None)),
            out_specs=P(None, None), check_vma=False,
        )(g2_p, b_p, sg_a, sw_a)
        if pk and m00:
            # global Cout shard-padding correction: once, after the psum
            out = out - jnp.asarray(pk * m00, out.dtype)
        return out

    return fn


def bwd_gemms(ctx: MeshContext, part: GemmPartition
              ) -> tuple[Callable[[Array, Array], Array],
                         Callable[[Array, Array], Array]]:
    """The STE backward GEMMs with specs matching the forward partition:
    ``gx = g @ wf.T`` comes back row-sharded like the activations, ``gw =
    xf.T @ g`` column-sharded like the weights. Each local matmul contracts
    the *full* reduction dim (the counterpart operand is replicated), so
    gradients are bitwise identical to the unsharded backward.
    """
    mesh = ctx.mesh

    def gx_fn(g: Array, wf: Array) -> Array:
        M = g.shape[0]
        pm = (-M) % part.n_rows
        g_p = jnp.pad(g, ((0, pm), (0, 0))) if pm else g
        out = jax.shard_map(
            lambda gb, wb: gb @ wb.T, mesh=mesh,
            in_specs=(P(part._dim(part.rows), None), P(None, None)),
            out_specs=P(part._dim(part.rows), None),
            check_vma=False)(g_p, wf)
        return out[:M]

    def gw_fn(xf: Array, g: Array) -> Array:
        N = g.shape[1]
        pn = (-N) % part.n_cols
        g_p = jnp.pad(g, ((0, 0), (0, pn))) if pn else g
        out = jax.shard_map(
            lambda xb, gb: xb.T @ gb, mesh=mesh,
            in_specs=(P(None, None), P(None, part._dim(part.cols))),
            out_specs=P(None, part._dim(part.cols)),
            check_vma=False)(xf, g_p)
        return out[:, :N]

    return gx_fn, gw_fn
