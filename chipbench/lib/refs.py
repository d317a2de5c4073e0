"""Plain float32 references of what the timed paths compute.

Written from the paper's equations and the public JAX/SciPy APIs, importing
nothing of the system under test: the RF-TCA fit (Algorithm 1, with a
full-precision statistics pass in blocks of samples and dense whitening), the out-of-sample transform of a seed-fused aligner, and one
FedRF-TCA round (Algorithms 2-5).

``dot`` selects the matmul precision of the heavy contractions:
``dot_highest`` is float32 (the precision the configurations state);
``dot_high`` is the three-pass bfloat16 product (``Precision.HIGH`` on a
TPU, written out on backends that ignore it).  The control of every cell is
the reference with ``dot_high``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dot_highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _split_bf16(a):
    """a = hi + lo + O(2^-16 |a|), hi and lo bfloat16.  reduce_precision keeps
    the compiler from folding the round trip through bfloat16 away."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def dot_high(a, b):
    """a @ b as three bfloat16 passes with float32 accumulation (hi*hi +
    hi*lo + lo*hi): ``Precision.HIGH`` on a TPU, written out elsewhere,
    where the backend computes every float32 product in full."""
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    a_hi, a_lo = _split_bf16(a)
    b_hi, b_lo = _split_bf16(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))


DOTS = {"highest": dot_highest, "high": dot_high}


# --------------------------------------------------------------------------
# random features
# --------------------------------------------------------------------------


def gauss_omega(seed: int, n_features: int, p: int, sigma: float = 1.0):
    """Omega ~ N(0, 1/sigma^2) drawn with jax.random from PRNGKey(seed): the
    draw ``rf_tca_fit`` documents for its default (non-fused) path."""
    return jax.random.normal(jax.random.PRNGKey(seed), (n_features, p), jnp.float32) / sigma


def fused_omega(seed: int, n_features: int, p: int):
    """Omega of the seed-fused stream: element (r, c) is Box-Muller on the
    threefry-2x32 words of key (seed, 0) and counter (r, c), with 24-bit
    uniforms.  Drawn here with JAX's own threefry primitive."""
    from jax.extend.random import threefry_2x32

    rows = jnp.broadcast_to(jnp.arange(n_features, dtype=jnp.uint32)[:, None], (n_features, p))
    cols = jnp.broadcast_to(jnp.arange(p, dtype=jnp.uint32)[None, :], (n_features, p))
    key = jnp.asarray([np.uint32(seed & 0xFFFFFFFF), np.uint32(0)], jnp.uint32)
    bits = threefry_2x32(key, jnp.concatenate([rows.ravel(), cols.ravel()]))
    b0, b1 = bits[: rows.size], bits[rows.size:]

    def uniform(b):
        return (b >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0**-24)

    r = jnp.sqrt(-2.0 * jnp.log1p(-uniform(b0)))
    return (r * jnp.cos(jnp.float32(2 * np.pi) * uniform(b1))).reshape(n_features, p)


def rff(x, omega, dot=dot_highest):
    """Sigma = [cos(Omega X); sin(Omega X)] / sqrt(N): (2N, n)."""
    z = dot(omega, x)
    return jnp.concatenate([jnp.cos(z), jnp.sin(z)], axis=0) / jnp.sqrt(
        jnp.float32(omega.shape[0])
    )


# --------------------------------------------------------------------------
# RF-TCA fit
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block", "precision"))
def fit_stats(x, ell, omega, *, block: int, precision: str):
    """(G_H = Sigma H Sigma^T, u = Sigma ell, ) accumulated over sample blocks;
    Sigma (2N, n) never exists whole."""
    dot = DOTS[precision]
    p, n = x.shape
    pad = (-n) % block
    nb = (n + pad) // block
    xb = jnp.pad(x, ((0, 0), (0, pad))).reshape(p, nb, block).transpose(1, 0, 2)
    eb = jnp.pad(ell, (0, pad)).reshape(nb, block)
    mb = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad)).reshape(nb, block)
    two_n = 2 * omega.shape[0]

    def body(carry, blk):
        g, u, s = carry
        xk, ek, mk = blk
        sig = rff(xk, omega, dot) * mk[None, :]
        return (g + dot(sig, sig.T), u + dot(sig, ek[:, None])[:, 0], s + sig.sum(axis=1)), None

    init = (jnp.zeros((two_n, two_n), jnp.float32), jnp.zeros((two_n,), jnp.float32),
            jnp.zeros((two_n,), jnp.float32))
    (g, u, s), _ = jax.lax.scan(body, init, (xb, eb, mb))
    g_h = g - jnp.outer(s, s) / n
    return 0.5 * (g_h + g_h.T), u


@jax.jit
def _whitened(g_h, u, gamma):
    """B^{-1/2} G_H B^{-1/2} and B^{-1/2} for B = gamma I + u u^T, with
    B^{-1/2} built as a dense matrix from B's eigenvalues (gamma + |u|^2
    along u, gamma elsewhere)."""
    uu = jnp.sum(u * u)
    uhat = u / jnp.sqrt(uu)
    scale = jax.lax.rsqrt(gamma + uu) - jax.lax.rsqrt(gamma)
    half = jax.lax.rsqrt(gamma) * jnp.eye(u.shape[0], dtype=jnp.float32) + scale * jnp.outer(
        uhat, uhat)
    c = dot_highest(dot_highest(half, g_h), half)
    return 0.5 * (c + c.T), half


@jax.jit
def _unwhiten(half, vecs):
    return dot_highest(half, vecs)


def ell_of(n_s: int, n_t: int):
    return jnp.concatenate([jnp.full((n_s,), 1.0 / n_s, jnp.float32),
                            jnp.full((n_t,), -1.0 / n_t, jnp.float32)])


def solve_top(g_h, u, gamma: float, k: int):
    """Top-k eigenpairs of G_H w = lambda (gamma I + u u^T) w, largest first:
    whitening by a dense B^{-1/2} on the device, a symmetric eigensolve
    (SciPy) on the host."""
    from scipy.linalg import eigh

    c, half = _whitened(g_h, u, jnp.float32(gamma))
    two_n = c.shape[0]
    vals, vecs = eigh(np.asarray(c), subset_by_index=[two_n - k, two_n - 1])
    vals, vecs = vals[::-1].copy(), vecs[:, ::-1].copy()
    return np.asarray(vals, np.float32), _unwhiten(half, jnp.asarray(vecs, jnp.float32))


def fit_reference(x_s, x_t, omega, *, m: int, gamma: float = 1.0, block: int = 2048,
                  precision: str = "highest") -> dict:
    """Reference fit: statistics, the top m+1 eigenvalues and W_RF (2N, m)."""
    x = jnp.concatenate([x_s, x_t], axis=1)
    ell = ell_of(x_s.shape[1], x_t.shape[1])
    g_h, u = fit_stats(x, ell, omega, block=min(block, x.shape[1]), precision=precision)
    vals, w = solve_top(g_h, u, gamma, m + 1)
    return {"g_h": g_h, "u": u, "eigvals": vals, "w_rf": w[:, :m], "gamma": gamma}


def fit_numbers(ref: dict, w_rf, eigvals, omega, x_probe) -> dict:
    """How far a fitted (W_RF, eigenvalues) lies from the reference fit.

    - ``eig_rel``: largest relative gap of the m eigenvalues;
    - ``w_resid``: largest relative residual ||G_H w - lambda B w|| /
      (lambda ||B w||) of the fitted pairs in the reference problem;
    - ``aligned_rel``: relative Frobenius gap of the Gram of the aligned
      probe features F = W^T Sigma(X_probe), which no sign or rotation of
      W's columns changes.
    """
    m = int(np.shape(eigvals)[0])
    lam_r = jnp.asarray(ref["eigvals"][:m])
    lam = jnp.asarray(eigvals, jnp.float32)
    eig_rel = float(jnp.max(jnp.abs(lam - lam_r) / jnp.abs(lam_r)))
    w = jnp.asarray(w_rf, jnp.float32)
    u = ref["u"]
    gw = dot_highest(ref["g_h"], w)
    bw = ref["gamma"] * w + jnp.outer(u, dot_highest(u[None, :], w)[0])
    resid = jnp.linalg.norm(gw - bw * lam[None, :], axis=0) / (
        jnp.abs(lam) * jnp.linalg.norm(bw, axis=0)
    )
    sig = rff(x_probe, omega)
    f_p = dot_highest(w.T, sig)
    f_r = dot_highest(ref["w_rf"].T, sig)
    k_p = dot_highest(f_p.T, f_p)
    k_r = dot_highest(f_r.T, f_r)
    aligned_rel = float(jnp.linalg.norm(k_p - k_r) / jnp.linalg.norm(k_r))
    gap = float(ref["eigvals"][m - 1] - ref["eigvals"][m]) / float(ref["eigvals"][m - 1])
    return {"eig_rel": eig_rel, "w_resid": float(jnp.max(resid)),
            "aligned_rel": aligned_rel, "eig_gap_m": gap}


# --------------------------------------------------------------------------
# out-of-sample transform (serving)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("precision",))
def _transform(w_rf, omega, x, *, precision: str):
    dot = DOTS[precision]
    return dot(w_rf.T, rff(x, omega, dot))


def transform_columns(w_rf, omega, x, *, width: int = 256, precision: str = "highest"):
    """F = W_RF^T Sigma(X) (m, n) for n <= width columns, computed on a block
    padded to ``width`` columns (one compiled program for every n; columns
    do not mix)."""
    x = np.asarray(x, np.float32)
    n = x.shape[1]
    block = np.zeros((x.shape[0], width), np.float32)
    block[:, :n] = x
    return np.asarray(_transform(jnp.asarray(w_rf), omega, jnp.asarray(block),
                                 precision=precision))[:, :n]


# --------------------------------------------------------------------------
# one FedRF-TCA round (Algorithms 2-5), float32
# --------------------------------------------------------------------------


def fed_omega(seed: int, n_rff: int, d: int, sigma: float = 1.0):
    """The shared Omega (N, d) of Algorithm 5, drawn with jax.random from
    PRNGKey(seed) as the protocol documents."""
    return jax.random.normal(jax.random.PRNGKey(seed), (n_rff, d)) / sigma


def fed_init(key, input_dim: int, widths: tuple, n_rff: int, m: int, n_classes: int):
    """The shared initial model every client starts from (paper Fig. 1):
    He-normal extractor layers, W_RF ~ N(0, 1/2N), classifier ~ N(0, 1/m)."""
    keys = jax.random.split(key, len(widths) + 2)
    dims = (input_dim,) + tuple(widths)
    ext = [{"w": jax.random.normal(keys[i], (a, b)) * jnp.sqrt(2.0 / a), "b": jnp.zeros((b,))}
           for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]
    w_rf = jax.random.normal(keys[-2], (2 * n_rff, m)) / jnp.sqrt(2 * n_rff)
    clf = {"w": jax.random.normal(keys[-1], (m, n_classes)) / jnp.sqrt(m),
           "b": jnp.zeros((n_classes,))}
    return {"extractor": ext, "w_rf": w_rf, "classifier": clf}


def _features(params, omega, x, dot):
    """RFF rows (n, 2N) of the unit-normalized extractor output of X (p, n)."""
    h = x.T
    layers = params["extractor"]
    for i, layer in enumerate(layers):
        h = dot(h, layer["w"]) + layer["b"]
        if i < len(layers) - 1:
            h = jax.nn.gelu(h)
    h = h / (jnp.linalg.norm(h, axis=-1, keepdims=True) + 1e-6)
    return rff(h.T, omega, dot).T


def _moment(params, omega, x, sign, dot):
    """Sigma ell: sign times the mean RFF row (eq. 2)."""
    return sign * jnp.mean(_features(params, omega, x, dot), axis=0)


def _source_loss(params, omega, x, y, tgt_msg, gate, lam, n_classes, dot):
    feats = _features(params, omega, x, dot)
    logits = dot(dot(feats, params["w_rf"]), params["classifier"]["w"]) + params["classifier"]["b"]
    ce = -jnp.mean(jnp.sum(jax.nn.one_hot(y, n_classes) * jax.nn.log_softmax(logits), axis=-1))
    v = dot((jnp.mean(feats, axis=0) + tgt_msg)[None, :], params["w_rf"])[0]
    return ce + lam * gate * jnp.sum(v * v)


def _target_loss(params, omega, x, msgs, weights, dot):
    msg_t = _moment(params, omega, x, -1.0, dot)
    v = dot(msgs + msg_t[None, :], params["w_rf"])
    return jnp.sum(weights * jnp.sum(v * v, axis=1)) / jnp.maximum(jnp.sum(weights), 1e-9)


def adam_step(params, opt, grads, lr, b1=0.9, b2=0.999, eps=1e-8):
    step = opt["step"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"], grads)
    bc1, bc2 = 1 - b1**step, 1 - b2**step
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), params, mu, nu)
    return new, {"step": step, "mu": mu, "nu": nu}


def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"step": 0, "mu": zeros, "nu": jax.tree_util.tree_map(jnp.zeros_like, params)}


def fed_round(src, src_opt, tgt, tgt_opt, batch, plan, *, omega, lr, lam, n_classes,
              classifier_round: bool, precision: str = "highest"):
    """One synchronous round: target broadcast, source local steps (MMD for
    clients in A), target step on the delivered moments, W_RF merge over B
    and the target, classifier merge over C on classifier rounds.

    ``src``/``src_opt``: lists over clients; ``batch``: per-client lists
    ``xs``, ``ys``, ``x_msg`` and the target's ``xt``, ``xt_msg``; ``plan``:
    (A, B, C) client-index lists.  Returns the new state and each source's
    and the target's gradient (None for a target that took no step)."""
    dot = DOTS[precision]
    a_set, b_set, c_set = (set(s) for s in plan)
    k = len(src)
    tgt_msg = _moment(tgt, omega, batch["xt_msg"], -1.0, dot)
    src_grads, new_src, new_opt = [], [], []
    for i in range(k):
        gate = 1.0 if i in a_set else 0.0
        g = jax.grad(_source_loss)(src[i], omega, batch["xs"][i], batch["ys"][i], tgt_msg,
                                   gate, lam, n_classes, dot)
        p, o = adam_step(src[i], src_opt[i], g, lr)
        src_grads.append(g)
        new_src.append(p)
        new_opt.append(o)
    tgt_grad = None
    if a_set:
        msgs = jnp.stack([_moment(new_src[i], omega, batch["x_msg"][i], 1.0, dot)
                          for i in range(k)])
        weights = jnp.asarray([1.0 if i in a_set else 0.0 for i in range(k)], jnp.float32)
        tgt_grad = jax.grad(_target_loss)(tgt, omega, batch["xt"], msgs, weights, dot)
        tgt, tgt_opt = adam_step(tgt, tgt_opt, tgt_grad, lr)
    if b_set:
        w_avg = (sum(new_src[i]["w_rf"] for i in sorted(b_set)) + tgt["w_rf"]) / (len(b_set) + 1)
        for i in b_set:
            new_src[i] = {**new_src[i], "w_rf": w_avg}
        tgt = {**tgt, "w_rf": w_avg}
    if classifier_round and c_set:
        c_avg = jax.tree_util.tree_map(
            lambda *leaves: sum(leaves) / max(len(c_set), 1.0),
            *[new_src[i]["classifier"] for i in sorted(c_set)])
        for i in c_set:
            new_src[i] = {**new_src[i], "classifier": c_avg}
        tgt = {**tgt, "classifier": c_avg}
    return new_src, new_opt, tgt, tgt_opt, src_grads, tgt_grad


# one compiled program per plan and kind of round; ``plan`` as a tuple of tuples
fed_round_jit = jax.jit(fed_round, static_argnames=(
    "plan", "lr", "lam", "n_classes", "classifier_round", "precision"))


def fed_round_from(cfg, proto, src, src_opt, tgt, tgt_opt, batch, t, plan, *,
                   precision: str = "highest"):
    """``fed_round`` as round ``t`` of a federation with client settings
    ``cfg`` and protocol settings ``proto``: the Omega the protocol documents,
    ``plan``'s ``msg_clients``, ``w_clients`` and ``c_clients`` as A, B, C, and a
    classifier round where t = 0 mod T_C."""
    return fed_round_jit(
        src, src_opt, tgt, tgt_opt, batch,
        tuple(tuple(s) for s in (plan.msg_clients, plan.w_clients, plan.c_clients)),
        omega=fed_omega(cfg.rff_seed, cfg.n_rff, cfg.extractor_widths[-1], cfg.rff_sigma),
        lr=proto.lr, lam=cfg.lambda_mmd, n_classes=cfg.n_classes,
        classifier_round=(t % proto.t_c == 0), precision=precision)


def leaf_gaps(prog_leaves, ref_leaves, keep) -> np.ndarray:
    """Each leaf's |norm(program) - norm(reference)| over the larger of the
    reference leaf's norm and the median reference leaf norm, for the leaves
    ``keep`` marks."""
    ref_n = np.asarray([float(jnp.linalg.norm(r)) for r in ref_leaves])
    prog_n = np.asarray([float(jnp.linalg.norm(p)) for p in prog_leaves])
    med = float(np.median(ref_n[keep])) if np.any(keep) else 0.0
    gaps = np.abs(prog_n - ref_n) / np.maximum(np.maximum(ref_n, med), 1e-30)
    return gaps[keep]


def leaf_gap(prog_leaves, ref_leaves, keep) -> float:
    """The worst of ``leaf_gaps`` (0 with no leaf kept)."""
    gaps = leaf_gaps(prog_leaves, ref_leaves, keep)
    return float(np.max(gaps)) if gaps.size else 0.0


def leaf_rel(prog_leaves, ref_leaves, keep) -> float:
    """Worst leaf's norm(program - reference) over the larger of the
    reference leaf's norm and the median reference leaf norm, over the leaves
    ``keep`` marks (float64 on the host)."""
    ref = [np.asarray(r, np.float64) for r in ref_leaves]
    ref_n = np.asarray([np.linalg.norm(r) for r in ref])
    diff_n = np.asarray([np.linalg.norm(np.asarray(p, np.float64) - r)
                         for p, r in zip(prog_leaves, ref)])
    med = float(np.median(ref_n[keep])) if np.any(keep) else 0.0
    rel = diff_n / np.maximum(np.maximum(ref_n, med), 1e-30)
    return float(np.max(rel[keep])) if np.any(keep) else 0.0
