"""The plain reference: a SwiGLU llama decoder in straightforward
``jax.numpy`` at float32 (``highest`` matmul precision), with every
projection and the head through the configuration's approximate
multiplier, and AdamW.

It imports nothing of the program. The multiplier is computed from its
published definition, not from a table: the broken-array multiplier
``mul8s_1L2H`` (8-bit signed, partial-product diagonals ``i + j < 5``
left out) gives

    M[a, w] = sign(a) sign(w) sum_{i + j >= k} a_i w_j 2^(i+j)

over the bits ``a_i``, ``w_j`` of |a| and |w|. For each bit ``i`` of the
activation that is ``sign(a) a_i`` (in {-1, 0, 1}) times
``2^i sign(w) (|w| with its low max(0, k - i) bits cleared)`` (in
[-128, 127]), so ``sum_k M[a, w]`` is eight exact int8 x int8 -> int32
matrix products.

Quantization follows the emulator's published scheme (paper eq. 1-2):
per-tensor symmetric activations, per-output-channel symmetric weights,
codes ``clip(round(x / s), -128, 127)``, dequantized by the product of the
two scales. The backward is the straight-through estimator whose two
gradient GEMMs run through the same multiplier on per-tensor symmetric
codes of the incoming gradient and the fake-quantized residuals.

``dtype`` (or ``store``) is the precision of the stored tensors: float32
for the reference, one step lower for the control that stands in the
program's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def multiplier_einsum(mult: dict, spec: str = "mk,kn->mn"):
    """``f(a, w)``: ``einsum(spec)`` over products ``M[a, w]`` of int32
    codes (``spec`` contracts like a matrix product; the default is
    ``sum_k M[a[m, k], w[k, n]]``), in int32, for the configuration's
    multiplier: one int8 product per bit of ``|a|``."""
    if mult["family"] != "broken_array" or mult["bits"] != 8:
        raise ValueError(f"no reference for multiplier {mult}")
    k = mult["broken_diagonals"]

    def f(a, w):
        sa, ma = jnp.sign(a), jnp.abs(a)
        sw, mw = jnp.sign(w), jnp.abs(w)
        acc = 0
        for i in range(8):
            ai = (sa * ((ma >> i) & 1)).astype(jnp.int8)
            keep = ~((1 << max(0, k - i)) - 1)
            wi = (sw * (mw & keep)).astype(jnp.int8)
            acc = acc + (jnp.einsum(spec, ai, wi,
                                    preferred_element_type=jnp.int32) << i)
        return acc
    return f


def multiplier_table(mult: dict) -> np.ndarray:
    """The 256 x 256 product table ``M[a + 128, w + 128]`` (for tests)."""
    v = np.arange(-128, 128, dtype=np.int64)
    a, w = np.meshgrid(v, v, indexing="ij")
    k = mult["broken_diagonals"]
    acc = np.zeros_like(a)
    for i in range(8):
        keep = ~((1 << max(0, k - i)) - 1)
        acc += ((np.abs(a) >> i) & 1) * (np.abs(w) & keep) << i
    return np.sign(a) * np.sign(w) * acc


def _codes(x, s):
    return jnp.clip(jnp.round(x / s), -128, 127).astype(jnp.int32)


def _fwd_scale(amax):
    return jnp.maximum(amax, 1e-12) / 127.0


def _bwd_scale(amax):
    return jnp.maximum(amax, 1e-12) * (1.0 / 127.0)


def approx_dense(gemm, dtype):
    """``y = x @ w`` through the multiplier, with the approximate STE
    backward; ``x`` (M, K), ``w`` (K, N)."""

    def forward(x, w):
        x = x.astype(F32)
        w = w.astype(F32)
        xs = _fwd_scale(jnp.maximum(jnp.max(jnp.abs(x)), 1e-6))
        ws = _fwd_scale(jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-9))
        xq, wq = _codes(x, xs), _codes(w, ws[None, :])
        y = gemm(xq, wq).astype(F32) * (xs * ws)[None, :]
        xf = (xq.astype(F32) * xs).astype(dtype)
        wf = (wq.astype(F32) * ws[None, :]).astype(dtype)
        return y.astype(dtype), (xf, wf)

    @jax.custom_vjp
    def dense(x, w):
        return forward(x, w)[0]

    def bwd(res, g):
        xf, wf = res
        g, xf, wf = g.astype(F32), xf.astype(F32), wf.astype(F32)
        sg = _bwd_scale(jnp.max(jnp.abs(g)))
        sx = _bwd_scale(jnp.max(jnp.abs(xf)))
        sw = _bwd_scale(jnp.max(jnp.abs(wf)))
        gx = gemm(_codes(g, sg), _codes(wf.T, sw)).astype(F32) * (sg * sw)
        gw = gemm(_codes(xf.T, sx), _codes(g, sg)).astype(F32) * (sx * sg)
        return gx.astype(dtype), gw.astype(dtype)

    dense.defvjp(forward, bwd)
    return dense


def _rms(x, w, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * freqs            # (S, d/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _attention(q, k, v):
    """Exact causal GQA softmax attention; q (B, S, H, D), k/v (B, S, Hkv, D)."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2).astype(F32)
    v = jnp.repeat(v, rep, axis=2).astype(F32)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32), k) / d ** 0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def make_logits_batch(cfg: dict, dtype, store=None):
    """``logits(params, tokens)``: (B, S, V) float32 logits of the decoder
    over the program's parameter layout, stored tensors in ``dtype``, or in
    float32 passed through ``store`` after every op when it is given."""
    gemm = multiplier_einsum(cfg["multiplier"])
    dense2 = approx_dense(gemm, dtype)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    st = store or (lambda x: x)

    def dense(x, w):
        lead = x.shape[:-1]
        return st(dense2(x.reshape(-1, x.shape[-1]), w).reshape(*lead, -1))

    def layer(x, p):
        b, s, _ = x.shape
        pos = jnp.arange(s)
        a = st(_rms(x, p["norm1"], eps))
        q = st(_rope(dense(a, p["wq"]).reshape(b, s, h, hd), pos, theta))
        k = st(_rope(dense(a, p["wk"]).reshape(b, s, hkv, hd), pos, theta))
        v = dense(a, p["wv"]).reshape(b, s, hkv, hd)
        x = st(x + dense(st(_attention(q, k, v)).reshape(b, s, h * hd), p["wo"]))
        m = st(_rms(x, p["norm2"], eps))
        up = st(st(jax.nn.silu(dense(m, p["w_gate"]).astype(F32)).astype(dtype))
                * dense(m, p["w_up"]))
        return st(x + dense(up, p["w_down"])), None

    def logits(params, tokens):
        params = jax.tree.map(st, params)
        g = params["groups"]["b0"]
        stacked = {"norm1": g["norm1"]["w"], "norm2": g["norm2"]["w"],
                   **g["attn"], **g["mlp"]}
        x = st(params["embed"][tokens].astype(dtype))
        x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
        x = st(_rms(x, params["final_norm"]["w"][0], eps))
        head = (params["embed"].T if cfg["tie_word_embeddings"]
                else params["lm_head"])
        return dense(x, head).astype(F32)

    return logits


def int8_store(x):
    """Round a tensor through per-tensor symmetric int8 and back."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    return (jnp.clip(jnp.round(x / s), -127, 127) * s).astype(x.dtype)


def token_nll(logits, labels):
    """(B, S) next-token negative log-likelihood."""
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return logz - gold


def make_loss(cfg: dict, dtype):
    """``loss(params, tokens, labels)``: mean next-token cross entropy."""
    logits = make_logits_batch(cfg, dtype)
    return lambda params, tokens, labels: jnp.mean(
        token_nll(logits(params, tokens), labels))


def token_nlls(cfg: dict, params, batches, precision: str = "float32") -> list:
    """Each token's next-token NLL (B, S) over ``batches``, scored as the
    batches they came in (the quantization scales are per batch), with
    every stored tensor in ``precision``: a float dtype, or ``int8``
    (per-tensor symmetric int8 round trips in float32)."""
    if precision == "int8":
        dtype, store = F32, int8_store
    else:
        dtype, store = jnp.dtype(precision), None
    logits = make_logits_batch(cfg, dtype, store)
    p = jax.tree.map(lambda x: x.astype(dtype), params)

    @jax.jit
    def score(p, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return token_nll(logits(p, tokens), labels)

    return [np.asarray(score(p, jnp.asarray(b["tokens"]),
                             jnp.asarray(b["labels"]))) for b in batches]


def lr_at(opt: dict, step):
    """Linear warm-up then cosine decay to a tenth."""
    step = jnp.asarray(step, F32)
    base, warm, total = opt["lr"], opt["warmup"], opt["total"]
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = base * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < warm, base * step / max(warm, 1), cos)


def make_train_step(cfg: dict, opt: dict, dtype):
    """One AdamW step with global-norm clipping and decoupled weight decay
    on every leaf: ``(params, mu, nu, t, batch) -> (params, mu, nu, loss,
    clipped grads)``."""
    loss = make_loss(cfg, dtype)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    @jax.jit
    def step(params, mu, nu, t, tokens, labels):
        with jax.default_matmul_precision("highest"):
            l, g = jax.value_and_grad(loss)(params, tokens, labels)
        g = jax.tree.map(lambda x: x.astype(F32), g)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, opt["clip_norm"]
                                                   / (gn + 1e-9)), g)
        t = t + 1
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        bc1, bc2 = 1 - b1 ** t.astype(F32), 1 - b2 ** t.astype(F32)
        lr = lr_at(opt, t)

        def upd(p, m, v):
            u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p.astype(F32)
            return (p.astype(F32) - lr * u).astype(p.dtype)

        return jax.tree.map(upd, params, mu, nu), mu, nu, l, g

    return step


def leaf_norms(tree) -> dict:
    """{path: float32 norm} of every leaf, fetched to the host."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                                for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def diff_norms(a, b) -> dict:
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree.leaves(b)
    norms = jax.jit(lambda xs, ys: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32))))
        for x, y in zip(xs, ys)])([x for _, x in flat_a], flat_b)
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat_a, norms)}


def train_readings(cfg: dict, opt: dict, params0, batches, steps: int,
                   dtype=F32, fault=None) -> dict:
    """The observables of the first ``steps`` steps from ``params0``:
    each step's loss, the first step's clipped gradient norms per leaf and
    the parameters' change per leaf after the last step. ``fault`` plants a
    fault for the harness's own checks: ``"half_batch"``, the loss over
    the first half of the rows."""
    step = make_train_step(cfg, opt, dtype)
    p = jax.tree.map(lambda x: x.astype(dtype), params0)
    mu = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), p)
    nu = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), p)
    t = jnp.zeros((), jnp.int32)
    losses, first_grad = [], None
    for i in range(steps):
        b = batches[i]
        tokens, labels = np.asarray(b["tokens"]), np.asarray(b["labels"])
        if fault == "half_batch":
            tokens, labels = tokens[: len(tokens) // 2], labels[: len(labels) // 2]
        p, mu, nu, l, g = step(p, mu, nu, t, jnp.asarray(tokens),
                               jnp.asarray(labels))
        t = t + 1
        losses.append(float(l))
        if i == 0:
            first_grad = leaf_norms(g)
        del g
    change = diff_norms(p, params0)
    return {"losses": losses, "first_grad": first_grad, "change": change}


# ---------------------------------------------------------------------------
# serving: teacher-forced logits over prompt + served tokens, with the
# approximate attention of the emulated accelerator
# ---------------------------------------------------------------------------

def make_logits(cfg: dict, block: int, store):
    """``logits(params, tokens, n)``: (T, V) float32 logits of one sequence
    whose first ``n`` positions are real (the rest padding). Per-tensor
    scales are calibrated on the real positions. Attention is the emulated
    accelerator's: Q, K, V per-tensor int8 codes, QK^T and PV through the
    multiplier, an online softmax over key blocks of ``block`` positions
    whose probabilities enter PV as ``round(127 p)`` codes relative to the
    running maximum. ``store`` rounds every stored tensor to the precision
    under test (identity for the reference)."""
    mult = cfg["multiplier"]
    gemm = multiplier_einsum(mult)
    qk = multiplier_einsum(mult, "htd,hdj->htj")
    pv = multiplier_einsum(mult, "htj,hjd->htd")
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    rep = h // hkv
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

    def amax(x, real):
        return jnp.max(jnp.where(real[:, None], jnp.abs(x), 0.0))

    def dense(x, w, real):
        xs = _fwd_scale(jnp.maximum(amax(x, real), 1e-6))
        ws = _fwd_scale(jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-9))
        y = gemm(_codes(x, xs), _codes(w, ws[None, :])).astype(F32)
        return store(y * (xs * ws)[None, :])

    def attention(q, k, v, real):
        t = q.shape[0]
        sq, sk, sv = (_bwd_scale(jnp.maximum(amax(z.reshape(t, -1), real), 1e-6))
                      for z in (q, k, v))
        qq = _codes(q, sq).transpose(1, 0, 2)                    # (H, T, D)
        kq = jnp.repeat(_codes(k, sk), rep, axis=1).transpose(1, 0, 2)
        vq = jnp.where(real[:, None, None], _codes(v, sv), 0)
        vq = jnp.repeat(vq, rep, axis=1).transpose(1, 0, 2)
        score = (sq * sk) * (1.0 / np.sqrt(hd))
        nb = t // block
        pos = jnp.arange(t)

        def body(carry, b):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(kq, b * block, block, 1)
            vb = jax.lax.dynamic_slice_in_dim(vq, b * block, block, 1)
            s = qk(qq, kb.transpose(0, 2, 1)).astype(F32) * score
            kpos = b * block + jnp.arange(block)
            mask = (kpos[None, :] <= pos[:, None]) & real[kpos][None, :]
            s = jnp.where(mask[None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(-1)
            pq = jnp.clip(jnp.round(p * 127.0), 0, 127).astype(jnp.int32)
            acc = acc * alpha[..., None] + pv(pq, vb).astype(F32)
            return (m_new, l, acc), None

        init = (jnp.full((h, t), -1e30, F32), jnp.zeros((h, t), F32),
                jnp.zeros((h, t, hd), F32))
        (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nb))
        out = acc * (sv * (1.0 / 127.0)) / jnp.maximum(l, 1e-30)[..., None]
        return store(out.transpose(1, 0, 2).reshape(t, h * hd))

    def layer(x, p, real):
        t = x.shape[0]
        pos = jnp.arange(t)
        a = store(_rms(x, p["norm1"], eps))
        q = _rope(dense(a, p["wq"], real).reshape(1, t, h, hd), pos, theta)[0]
        k = _rope(dense(a, p["wk"], real).reshape(1, t, hkv, hd), pos, theta)[0]
        v = dense(a, p["wv"], real).reshape(t, hkv, hd)
        x = store(x + dense(attention(store(q), store(k), v, real), p["wo"], real))
        m = store(_rms(x, p["norm2"], eps))
        up = store(jax.nn.silu(dense(m, p["w_gate"], real))
                   * dense(m, p["w_up"], real))
        return store(x + dense(up, p["w_down"], real))

    @jax.jit
    def logits(params, tokens, n):
        with jax.default_matmul_precision("highest"):
            real = jnp.arange(tokens.shape[0]) < n
            p = jax.tree.map(lambda w: store(w.astype(F32)), params)
            g = p["groups"]["b0"]
            stacked = {"norm1": g["norm1"]["w"], "norm2": g["norm2"]["w"],
                       **g["attn"], **g["mlp"]}
            x = store(p["embed"][tokens])
            x, _ = jax.lax.scan(lambda c, lp: (layer(c, lp, real), None),
                                x, stacked)
            x = store(_rms(x, p["final_norm"]["w"][0], eps))
            head = p["embed"].T if cfg["tie_word_embeddings"] else p["lm_head"]
            return dense(x, head, real)

    return logits


def rounding(name: str):
    """``store`` for a precision: identity for float32, else a round trip
    through ``name`` (``float8_e4m3fn``, ``bfloat16``)."""
    if name == "float32":
        return lambda x: x
    dt = jnp.dtype(name)
    return lambda x: x.astype(dt).astype(F32)


def served_gaps(cfg: dict, params, block: int, seqs, lengths: int,
                store_name: str = "float32", other: str | None = None):
    """For each (prompt, served tokens): how far each served token's
    reference logit lies below the reference's best at its position. With
    ``other`` the served tokens are replaced by what that precision's
    reference puts first at each position of the same prompt and tokens
    (the control)."""
    ref = make_logits(cfg, block, rounding(store_name))
    alt = make_logits(cfg, block, rounding(other)) if other else None
    gaps = []
    for prompt, served in seqs:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n = len(seq)
        padded = np.zeros(lengths, np.int32)
        padded[:n] = seq
        rows = slice(len(prompt) - 1, n)
        lr = np.asarray(ref(params, jnp.asarray(padded), n))[rows]
        pick = np.asarray(served)
        if alt is not None:
            la = np.asarray(alt(params, jnp.asarray(padded), n))[rows]
            pick = la.argmax(-1)
        gaps.append(lr.max(-1) - lr[np.arange(len(pick)), pick])
    return np.concatenate(gaps)
