"""Mamba-2 for the benchmark: seeded weights and the plain training reference.

``init_params`` makes the weights on the device in one jitted call from the
seed, laid out as the chain runtime takes them (a stack of 48 SSD blocks under
``stack.groups[0]``, the tied embedding, the final norm).  The reference and
the system under test both start from these weights.

The reference imports nothing of the system under test.  It is the model of
arXiv:2405.21060 written out in ``jax.numpy`` and float32 at the highest
matmul precision, one layer at a time:

    h   = rmsnorm(x) * (1 + ln1)
    z, x_, B, C, dt = h @ W_in                (widths Di, Di, N, N, H)
    x_, B, C = silu(causal depthwise conv(x_ | B | C))
    dt  = softplus(dt + dt_bias),  a = -exp(A_log) * dt
    y_t = sum_{s<=t} (C_t . B_s) exp(a_{s+1} + ... + a_t) dt_s x_s + D x_t
    x  += rmsnorm(y * silu(z)) * (1 + norm) @ W_out

then rmsnorm * (1 + final_norm), logits against the tied embedding, and the
mean next-token cross entropy.  The scan over time is the minimal chunked SSD
listing of the paper (its ``ssd_minimal_discrete``).  As the configuration
states, the conv has no bias and the residual stream is held in the compute
dtype (the published model has both; the configuration lists them as
changed).  Gradients are summed over blocks of rows, so that the published
widths fit; AdamW follows the configuration's hyperparameters and decays the
leaves it names.

``low`` selects the control: wherever the system computes in bfloat16, the
configuration's compute dtype, the control holds float8 e4m3, the step below:
the embedded tokens, the residual stream, norm outputs, projections and their
weights, the conv and gate activations, the head's operands, and the operands
of the SSD einsums (float32 arrays in the system, whose einsums run at the
TPU's default matmul precision, that is on bfloat16 operands).  AdamW stays
float32 in both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 2   # rows of the batch per reference gradient block


def key_for(seed: int, stream: int):
    """A PRNG key for one stream of one (any-size) integer seed."""
    s = int(seed) % (1 << 64)
    k = jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)
    return jax.random.fold_in(k, stream)


def check_config(cfg: dict) -> None:
    """Refuse what this model and the system under test cannot express:
    the published conv bias and float32 residual stream."""
    for key in ("conv_bias", "residual_in_fp32"):
        if cfg.get(key, False):
            raise ValueError(f"{cfg['name']}: {key} true is not supported")


def dims(m: dict) -> dict:
    D = m["d_model"]
    Di = m["ssm_expand"] * D
    H = Di // m["ssm_head_dim"]
    N = m["ssm_state"]
    return {"D": D, "Di": Di, "H": H, "P": m["ssm_head_dim"], "N": N,
            "C": Di + 2 * N, "F": 2 * Di + 2 * N + H, "L": m["n_layers"],
            "V": m["vocab_size"], "W": m["conv_width"]}


def init_params(m: dict, seed: int):
    """The weights for ``seed``, float32, in one jitted call."""
    d = dims(m)

    def make(key):
        ks = jax.random.split(key, 4)
        L, D, Di, H = d["L"], d["D"], d["Di"], d["H"]

        def normal(k, shape, scale):
            return jax.random.normal(k, shape, jnp.float32) * scale

        block = {
            "ln1": jnp.zeros((L, D), jnp.float32),
            "ssd": {
                "w_in": normal(ks[1], (L, D, d["F"]), 1.0 / math.sqrt(D)),
                "conv_w": normal(ks[2], (L, d["W"], d["C"]), 0.3),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.linspace(1.0, 16.0, H)), (L, H)),
                "D_skip": jnp.ones((L, H), jnp.float32),
                "dt_bias": jnp.zeros((L, H), jnp.float32),
                "norm": jnp.zeros((L, Di), jnp.float32),
                "w_out": normal(ks[3], (L, Di, D), 1.0 / math.sqrt(Di)),
            },
        }
        return {"embed": normal(ks[0], (d["V"], D), 0.02),
                "final_norm": jnp.zeros((D,), jnp.float32),
                "stack": {"groups": [block], "rem": []}}

    return jax.jit(make)(key_for(seed, 0))


def make_batches(m: dict, seed: int, n: int, batch: int, seq: int,
                 vocab_used: int) -> list:
    """``n`` batches of tokens and next-token targets, on the device."""
    def make(key):
        ids = jax.random.randint(key, (n, batch, seq + 1), 0, vocab_used,
                                 jnp.int32)
        return ids[:, :, :-1], ids[:, :, 1:]

    toks, tgts = jax.jit(make)(key_for(seed, 1))
    return [{"tokens": toks[i], "targets": tgts[i]} for i in range(n)]


# ------------------------------------------------------------- the reference
def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rounder(low: bool):
    """Where the system holds its compute dtype (bfloat16), the control
    holds float8 e4m3: ``q`` rounds there, and is the identity for the
    reference."""
    if not low:
        return lambda x: x
    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _segsum(x):
    """exp-ready segment sums: out[..., i, j] = x[j+1] + ... + x[i] for
    j <= i, -inf above the diagonal."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -jnp.inf)


def _ssd(X, A, B, C, Q, q):
    """The paper's minimal chunked SSD.  X (b, l, h, p) already scaled by
    dt; A (b, l, h) = dt * a; B, C (b, l, n), shared by the heads.  ``q``
    rounds the operands of every einsum (the control's float8)."""
    b, l, h, p = X.shape
    c = l // Q
    X = X.reshape(b, c, Q, h, p)
    B = B.reshape(b, c, Q, -1)
    C = C.reshape(b, c, Q, -1)
    A = jnp.moveaxis(A.reshape(b, c, Q, h), -1, 1)          # (b, h, c, Q)
    A_cum = jnp.cumsum(A, -1)

    def es(spec, *ops):
        return jnp.einsum(spec, *map(q, ops),
                          precision=jax.lax.Precision.HIGHEST)

    Lm = jnp.exp(_segsum(A))                                # (b, h, c, Q, Q)
    y_diag = es("bcln,bcsn,bhcls,bcshp->bclhp", C, B, Lm, X)
    decay = jnp.exp(A_cum[..., -1:] - A_cum)                # (b, h, c, Q)
    states = es("bcln,bhcl,bclhp->bchpn", B, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk = jnp.exp(_segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0),
                                                      (1, 0)))))
    states = es("bhzc,bchpn->bzhpn", chunk, states)[:, :-1]
    y_off = es("bcln,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cum))
    return (y_diag + y_off).reshape(b, l, h, p)


def _layer(m: dict, low: bool):
    d = dims(m)
    q = _rounder(low)
    eps = m["norm_eps"]

    def layer(x, p):
        s = p["ssd"]
        Bsz, S, _ = x.shape
        h = q(_rmsnorm(x, p["ln1"], eps))
        zx = q(_mm("bsd,df->bsf", h, q(s["w_in"])))
        Di, N = d["Di"], d["N"]
        z = zx[..., :Di]
        xbc = zx[..., Di:2 * Di + 2 * N]
        dt = zx[..., 2 * Di + 2 * N:]
        W = d["W"]
        pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
        w = q(s["conv_w"])
        conv = q(jax.nn.silu(sum(pad[:, i:i + S] * w[i] for i in range(W))))
        xs = conv[..., :Di].reshape(Bsz, S, d["H"], d["P"])
        Bm, Cm = conv[..., Di:Di + N], conv[..., Di + N:]
        dt = jax.nn.softplus(dt + s["dt_bias"])
        a = -jnp.exp(s["A_log"]) * dt
        y = _ssd(xs * dt[..., None], a, Bm, Cm, m["ssm_chunk"], q)
        y = q((y + s["D_skip"][:, None] * xs).reshape(Bsz, S, Di))
        y = q(_rmsnorm(q(y * jax.nn.silu(z)), s["norm"], eps))
        return q(x + q(_mm("bsi,id->bsd", y, q(s["w_out"])))), None

    return jax.checkpoint(layer)


def _nll_sum(m: dict, low: bool):
    q = _rounder(low)
    layer = _layer(m, low)

    def f(params, tokens, targets):
        x = q(params["embed"][tokens])
        x, _ = jax.lax.scan(layer, x, params["stack"]["groups"][0])
        x = q(_rmsnorm(x, params["final_norm"], m["norm_eps"]))
        logits = _mm("bsd,vd->bsv", x, q(params["embed"]))
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return jnp.sum(lse - gold)

    return f


def reference_steps(m: dict, opt: dict, params, batches: list, *,
                    low: bool = False, decayed=None) -> dict:
    """Train ``len(batches)`` AdamW steps from ``params``; returns the loss
    of each step, the first step's gradient and the change of the
    parameters after the last step, each as per-leaf norms.  Weight decay
    applies to the leaves the configuration names in ``opt["decayed"]``
    (or to ``decayed``), each layer's slice of a stacked leaf alike."""
    grad_sum = jax.jit(jax.value_and_grad(_nll_sum(m, low)))
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def lr(t):
        t = jnp.float32(t)
        if t < opt["warmup"]:
            return opt["lr"] * t / max(1.0, opt["warmup"])
        frac = min(1.0, (t - opt["warmup"]) / max(1.0, opt["total"]
                                                   - opt["warmup"]))
        return 0.5 * opt["lr"] * (1 + math.cos(math.pi * frac))

    decayed = set(opt["decayed"] if decayed is None else decayed)
    rate = jax.tree_util.tree_map_with_path(
        lambda path, _: wd if path[-1].key in decayed else 0.0, params)

    @jax.jit
    def adamw(p, g, mo, v, t, lr_t):
        mo = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, mo, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)

        def upd(p_, m_, v_, wd_):
            step = m_ / (1 - b1 ** t) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
            return p_ - lr_t * (step + wd_ * p_)

        return jax.tree.map(upd, p, mo, v, rate), mo, v

    p0 = params
    mo = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for t, batch in enumerate(batches, start=1):
        tok, tgt = batch["tokens"], batch["targets"]
        n = tok.shape[0] * tok.shape[1]
        total, grads = 0.0, None
        for r in range(0, tok.shape[0], ROW_BLOCK):
            s, g = grad_sum(params, tok[r:r + ROW_BLOCK],
                            tgt[r:r + ROW_BLOCK])
            total = total + s
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        grads = jax.tree.map(lambda x: x / n, grads)
        losses.append(float(total) / n)
        if g1 is None:
            g1 = leaf_norms(grads)
        params, mo, v = adamw(params, grads, mo, v, jnp.float32(t),
                              jnp.float32(lr(t)))
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return {"losses": losses, "grad1": g1, "delta": delta}


def leaf_norms(tree) -> list:
    """The float32 2-norm of every leaf, in tree order."""
    return [float(x) for x in jax.device_get(jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree.leaves(t)])(tree))]
