"""The plain reference: the cell's first training steps, written out anew.

Nothing here imports the trainer.  It follows the published descriptions:

* the model: token embedding; per layer RMSNorm -> grouped-query attention
  with rotary positions (rotate-half form, HF ``apply_rotary_pos_emb``),
  causal softmax at 1/sqrt(head_dim) -> output projection -> residual;
  RMSNorm -> SwiGLU MLP (``down(silu(gate x) * up x)``) -> residual; final
  RMSNorm; untied head over the vocabulary slice; mean next-token
  cross-entropy.  Float32 throughout, matrix products at ``highest``
  precision, attention one key/value head at a time under ``checkpoint`` so
  that it fits next to nothing else on one chip.
* the exchange (paper arXiv:1811.08596, section III-B): the worker's
  gradient leaves, concatenated in tree order, cut into chunks of ``chunk``
  values (zero padded); a real FFT of every chunk; the ``round((1 - theta)
  * bins)`` bins of largest Hermitian-weighted magnitude kept per chunk; the
  kept real and imaginary parts rounded to the nearest value of the 8-bit
  range float fitted to their [min, max] (``eps * 2**q * (1 + r / 2**m)``,
  the positive/negative code budget balanced in closed form); the mean of
  the workers' spectra; an inverse FFT.  ``dense`` takes the plain mean.
* the update: global-norm clipping, then AdamW with bias correction.

``precision="float8"`` rounds both operands of every matrix product to
float8 e4m3 first: the control, one step below the bfloat16 the
configuration computes in.  ``fault`` plants one of the faults the
comparison must catch, in the reference put in the trainer's place.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from bench import feed, weights

FAULTS = ("none", "frozen", "half_batch", "no_exchange")


def _mm(eq, a, b, precision):
    import jax
    import jax.numpy as jnp

    if precision == "float8":
        # operands rounded to float8 e4m3 on the way in; the backward pass
        # multiplies by the rounded operands and passes float32 cotangents
        a, b = (x + jax.lax.stop_gradient(
            x.astype(jnp.float8_e4m3fn).astype(jnp.float32) - x) for x in (a, b))
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """HF rotary embedding: x * cos + rotate_half(x) * sin, (B, S, H, D)."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([angles, angles], axis=-1)
    cos = jnp.asarray(np.cos(emb), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(emb), jnp.float32)[None, :, None, :]
    half = d // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(q, k, v, precision):
    """Causal softmax attention, q (B,S,H,D), k/v (B,S,Kh,D) -> (B,S,H,D),
    one key/value head (with its query group) at a time."""
    import jax
    import jax.numpy as jnp

    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, d).transpose(2, 0, 1, 3, 4)  # (Kh,B,S,G,D)
    kt = k.transpose(2, 0, 1, 3)  # (Kh,B,S,D)
    vt = v.transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one_head(args):
        qh, kh_, vh = args
        scores = _mm("bqgd,bkd->bgqk", qh, kh_, precision) / math.sqrt(d)
        scores = jnp.where(causal, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return _mm("bgqk,bkd->bqgd", p, vh, precision)

    out = jax.lax.map(one_head, (qg, kt, vt))  # (Kh,B,S,G,D)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, s, h, d)


def loss(params, tokens, targets, cfg, precision="float32"):
    """Mean next-token cross-entropy of the rows, float32."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    vocab = cfg["vocab_size"]
    x = params["embed"]["table"][tokens]
    stack = params["layers"]["l0_attn_mlp"]
    for layer in range(cfg["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[layer], stack)
        a = p["attn"]
        h = _rmsnorm(x, p["norm1"]["scale"], eps)
        q = _rotate(_mm("bsd,dhk->bshk", h, a["wq"], precision), theta)
        k = _rotate(_mm("bsd,dhk->bshk", h, a["wk"], precision), theta)
        v = _mm("bsd,dhk->bshk", h, a["wv"], precision)
        o = _attention(q, k, v, precision)
        x = x + _mm("bshk,hkd->bsd", o, a["wo"], precision)
        m = p["mlp"]
        h = _rmsnorm(x, p["norm2"]["scale"], eps)
        gate = _mm("bsd,df->bsf", h, m["gate"], precision)
        up = _mm("bsd,df->bsf", h, m["up"], precision)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["down"], precision)
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    head = (params["embed"]["head"] if "head" in params["embed"]
            else params["embed"]["table"].T)
    logits = _mm("bsd,dv->bsv", x, head[:, :vocab], precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


# ---------------------------------------------------------------------------
# the compressed exchange
# ---------------------------------------------------------------------------


def keep_count(bins: int, theta: float) -> int:
    return max(1, int(round((1.0 - theta) * bins)))


def range_float_fit(lo, hi, n_bits, m_bits):
    """(eps, positive code count P, negative code count) of the range float
    for kept values in [lo, hi]."""
    import jax.numpy as jnp

    n_codes, scale = 2 ** n_bits, 2 ** m_bits
    span = jnp.maximum(hi - lo, 1e-30)
    vmax = jnp.maximum(jnp.maximum(hi, span * 1e-6), 1e-30)
    vmag = jnp.maximum(-jnp.minimum(lo, -span * 1e-6), 1e-30)
    p = jnp.clip(jnp.round((n_codes - 1 + scale * jnp.log2(vmax / vmag)) / 2.0),
                 1, n_codes - 2)
    eps = jnp.maximum(vmax / 2.0 ** jnp.minimum((p - 1.0) / scale, 96.0), 1e-30)
    return eps, p, n_codes - 1 - p


def range_float_round(x, eps, p, n_neg, n_bits, m_bits):
    """Nearest value of the fitted range float: segment ``q = floor(log2(|x|
    / eps))`` holds ``2**m`` evenly spaced values, ties round up, magnitudes
    past the sign's largest code clip to it, and below ``eps`` to the nearer
    of 0 and ``eps``."""
    import jax.numpy as jnp

    scale = 2 ** m_bits
    a = jnp.abs(x)
    q = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(a, eps) / eps)), 0.0)
    base = eps * 2.0 ** q
    r = jnp.floor((a / base - 1.0) * scale + 0.5)
    near = base * (1.0 + r / scale)
    top = jnp.where(x >= 0, p, n_neg) - 1.0
    largest = eps * 2.0 ** jnp.floor(top / scale) * (1.0 + jnp.mod(top, scale) / scale)
    near = jnp.minimum(near, largest)
    near = jnp.where(a < eps, jnp.where(2.0 * a >= eps, eps, 0.0), near)
    return jnp.where(x >= 0, near, -near)


def kth_largest(values, k: int):
    """Each row's k-th largest non-negative value, exactly: a binary search
    over the float32 bit patterns (ordered as the values are) for the
    largest pattern that at least ``k`` of the row reach."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(values, jnp.int32)
    lo = jnp.zeros(bits.shape[:-1] + (1,), jnp.int32)
    hi = jnp.full_like(lo, 0x7F800001)

    def halve(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(bits >= mid, axis=-1, keepdims=True) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 31, halve, (lo, hi))
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


ROW_BLOCK = 2048


def _kept_spectrum(rows, chunk, theta):
    """Real FFT of (B, chunk) rows; kept real and imaginary parts (zeros
    elsewhere) and the keep mask."""
    import jax
    import jax.numpy as jnp

    bins = chunk // 2 + 1
    w = np.full((bins,), 2.0, np.float32)
    w[0] = 1.0
    if chunk % 2 == 0:
        w[-1] = 1.0
    spec = jnp.fft.rfft(rows, axis=-1)
    re, im = jnp.real(spec), jnp.imag(spec)
    mag = jnp.sqrt(re * re + im * im) * w
    keep = mag >= kth_largest(mag, keep_count(bins, theta))
    return jnp.where(keep, re, 0.0), jnp.where(keep, im, 0.0), keep


def exchange_into(acc, flat, ex, scale):
    """``acc + scale * roundtrip(flat)``: one worker's gradient through the
    compressed exchange, added onto the (rows, chunk) time-domain sum of the
    workers before it.  Two passes over blocks of rows: the kept values'
    range, then the rounded spectrum and its inverse FFT, so that only one
    block's spectrum is ever held."""
    import jax
    import jax.numpy as jnp

    chunk, theta = ex["chunk"], ex["theta"]
    rows = acc.shape[0]
    # materialized once: fused into the loops' slices, the concatenation of
    # the gradient's leaves would be recomputed for every block of rows
    x = jax.lax.optimization_barrier(
        jnp.pad(flat, (0, rows * chunk - flat.shape[0])).reshape(rows, chunk))
    block = min(ROW_BLOCK, rows)
    n_blocks = rows // block

    def rows_of(i):
        return jax.lax.dynamic_slice_in_dim(x, i * block, block)

    def span(i, lohi):
        re, im, keep = _kept_spectrum(rows_of(i), chunk, theta)
        lo = jnp.minimum(jnp.where(keep, re, jnp.inf).min(),
                         jnp.where(keep, im, jnp.inf).min())
        hi = jnp.maximum(jnp.where(keep, re, -jnp.inf).max(),
                         jnp.where(keep, im, -jnp.inf).max())
        return jnp.minimum(lohi[0], lo), jnp.maximum(lohi[1], hi)

    lo, hi = jax.lax.fori_loop(0, n_blocks, span,
                               (jnp.float32(jnp.inf), jnp.float32(-jnp.inf)))
    eps, p, n_neg = range_float_fit(lo, hi, ex["n_bits"], ex["m_bits"])

    def add(i, acc):
        re, im, keep = _kept_spectrum(rows_of(i), chunk, theta)
        rq = range_float_round(re, eps, p, n_neg, ex["n_bits"], ex["m_bits"])
        iq = range_float_round(im, eps, p, n_neg, ex["n_bits"], ex["m_bits"])
        spec = jax.lax.complex(jnp.where(keep, rq, 0.0), jnp.where(keep, iq, 0.0))
        back = jnp.fft.irfft(spec, n=chunk, axis=-1)
        here = jax.lax.dynamic_slice_in_dim(acc, i * block, block)
        return jax.lax.dynamic_update_slice_in_dim(acc, here + scale * back,
                                                   i * block, 0)

    return jax.lax.fori_loop(0, n_blocks, add, acc)


def exchange_rows(n: int, chunk: int) -> int:
    """Rows of the time-domain sum: whole chunks, in whole row blocks (the
    extra rows are zero and keep nothing but zeros)."""
    rows = -(-n // chunk)
    block = min(ROW_BLOCK, rows)
    return -(-rows // block) * block


# ---------------------------------------------------------------------------
# the steps
# ---------------------------------------------------------------------------


def _flat(tree):
    import jax
    import jax.numpy as jnp

    return jnp.concatenate([x.reshape(-1) for x in jax.tree_util.tree_leaves(tree)])


def _unflat(flat, like):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, at = [], 0
    for leaf in leaves:
        out.append(flat[at: at + leaf.size].reshape(leaf.shape))
        at += leaf.size
    return jax.tree_util.tree_unflatten(treedef, out)


def leaf_norms(tree) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in jax.tree_util.tree_leaves(t)]))(tree), np.float64)


def leaf_names(cfg: dict) -> list:
    import jax

    paths = jax.tree_util.tree_flatten_with_path(
        weights.shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]
    return [".".join(str(k.key) for k in path) for path, _ in paths]


def step_fns(cfg: dict, traffic: dict, chips: int, precision: str = "float32",
             fault: str = "none"):
    """The reference's jitted pieces: ``init(key)``, ``batches(key, step)``,
    ``worker(params, acc, tokens, targets, contributes=)`` -> (loss, acc')
    and ``update(params, mu, nu, acc, count)`` -> (params, mu, nu, clipped
    gradient leaf norms), with the shape of the workers' sum ``acc``."""
    import jax
    import jax.numpy as jnp

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ex = traffic["exchange"]
    dense = ex["reducer"] == "dense"
    train = cfg["training"]
    o = train["optimizer"]
    rows = traffic["rows_per_chip"]
    workers = 1 if fault == "no_exchange" else chips
    init = jax.jit(lambda k: weights.init(cfg, k))
    shapes = jax.eval_shape(init, weights.base_key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    acc_shape = (n,) if dense else (exchange_rows(n, ex["chunk"]), ex["chunk"])
    batches = jax.jit(feed.batch_fn(traffic, cfg["vocab_size"], rows * chips))
    @functools.partial(jax.jit, donate_argnums=(1,), static_argnames=("contributes",))
    def worker(params, acc, tokens, targets, contributes):
        val, g = jax.value_and_grad(loss)(params, tokens, targets, cfg, precision)
        if not contributes:
            return val, acc
        if dense:
            return val, acc + _flat(g) / workers
        return val, exchange_into(acc, _flat(g), ex, 1.0 / workers)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(params, mu, nu, acc, count):
        grads = _unflat(acc.reshape(-1)[:n], params)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, train["clip_norm"] / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g)))
                           for g in jax.tree_util.tree_leaves(grads)])
        if fault == "frozen":
            return params, mu, nu, jnp.zeros_like(norms)
        mu = jax.tree_util.tree_map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g,
                                    mu, grads)
        nu = jax.tree_util.tree_map(
            lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, nu, grads)
        c1, c2 = 1.0 - o["b1"] ** count, 1.0 - o["b2"] ** count
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - o["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + o["eps"])
                                           + o["weight_decay"] * p),
            params, mu, nu)
        return params, mu, nu, norms

    return init, batches, worker, update, acc_shape


def run(cfg: dict, traffic: dict, seed: int, chips: int, *,
        precision: str = "float32", fault: str = "none", steps: int = 3,
        devices=None) -> dict:
    """The reference's readings after ``steps`` steps from ``seed``: each
    step's loss, the first clipped gradient's leaf norms, and the leaf norms
    of the parameters' change.  Worker ``w`` runs on ``devices[w %
    len(devices)]`` (the default device alone without ``devices``), so the
    workers of a cell of several chips run side by side; their time-domain
    sums meet on the first device for the update."""
    import jax
    import jax.numpy as jnp

    init, batches, worker, update, acc_shape = step_fns(
        cfg, traffic, chips, precision, fault)
    devices = list(devices or jax.devices()[:1])
    home = devices[0]
    rows = traffic["rows_per_chip"]
    used = rows // 2 if fault == "half_batch" else rows
    workers = 1 if fault == "no_exchange" else chips
    key = weights.base_key(seed)
    params = jax.device_put(init(key), home)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for s in range(steps):
        batch = jax.device_put(batches(key, s), home)
        here = {home: params}
        accs, vals = {}, []
        for w in range(chips):
            dev = devices[w % len(devices)]
            if dev not in here:
                here[dev] = jax.device_put(params, dev)
            if dev not in accs:
                accs[dev] = jnp.zeros(acc_shape, jnp.float32, device=dev)
            lo = w * rows
            part = jax.device_put({k: v[lo: lo + used] for k, v in batch.items()}, dev)
            val, accs[dev] = worker(here[dev], accs[dev], part["tokens"],
                                    part["targets"], contributes=w < workers)
            vals.append(val)
        del here
        losses.append(float(np.mean([float(v) for v in vals])))
        acc = accs.pop(home)
        for dev in list(accs):
            acc = acc + jax.device_put(accs.pop(dev), home)
        params, mu, nu, norms = update(params, mu, nu, acc, jnp.float32(s + 1))
        if s == 0:
            first_grad = np.asarray(norms, np.float64)
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, init(key)))
    return {"loss": losses, "grad_norms": first_grad, "change_norms": change}
