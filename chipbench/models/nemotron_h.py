"""Nemotron-H for the benchmark: seeded weights and the plain training
reference of a chain node's share of the model.

``init_params`` makes the weights on the device in one jitted call from the
seed, laid out as the chain runtime takes them: the blocks of one period of
the layer pattern, stacked under ``stack.groups`` (one entry a pattern
position), the embedding, the final norm and the untied head.  The reference
and the system under test both start from these weights.

The reference imports nothing of the system under test.  It is Nemotron-H
(arXiv:2504.03624; the layer equations of ``nemotron_h`` in HF transformers)
written out in ``jax.numpy`` and float32 at the highest matmul precision, one
layer at a time.  Every block is ``x + mixer(rmsnorm(x) * (1 + ln1))``:

M, Mamba-2 (heads of P, G groups of B and C, each read by H / G heads):
    z, x_, B, C, dt = h @ W_in                (widths Di, Di, G N, G N, H)
    x_, B, C = silu(causal depthwise conv(x_ | B | C) + conv_b)
    dt  = softplus(dt + dt_bias),  a = -exp(A_log) * dt
    y_t = sum_{s<=t} (C_t . B_s)_g exp(a_{s+1} + ... + a_t) dt_s x_s + D x_t
    out = rmsnorm_per_group(y * silu(z)) * (1 + norm) @ W_out
  the scan is the paper's minimal chunked SSD (arXiv:2405.21060,
  ``ssd_minimal_discrete``) with B and C per group;
E, MoE (sigmoid router over all E experts, this node's ``Eh`` of them):
    s = sigmoid(h @ W_r) (float32);  top-k of s + bias, chosen by the biased
    scores and weighted by the unbiased ones, w = routed_scale * s_k / sum s_k
    out = sum_{e held} w_e relu(h @ U_e)^2 @ V_e + relu(h @ U_s)^2 @ V_s
  the held experts as a dense loop, each over every token with its weight
  (zero where the token did not choose it);
*, attention alone: GQA, causal, softmax(q k^T / sqrt(hd)) v @ W_o, no
  position embedding, no bias;

then rmsnorm * (1 + final_norm), logits against the untied head, and the
mean next-token cross entropy over the vocabulary slice.

Departures from the published model, each also the program's:
* norm scales are stored as offsets from 1 (``1 + w``, initialised 0);
* the residual stream is held in the compute dtype (the published config
  has ``residual_in_fp32`` false, so this is the published behaviour);
* a chain node's share: the experts outside ``[0, Eh)`` and their part of
  each MoE output are left out (the node holding them adds it), the
  vocabulary is the first ``vocab_size`` ids, and only one period of layers
  is held (the configuration file lists each cut);
* the correction bias is not trained: ``init_params`` sets it to the
  balance that training keeps, on tokens from the seed (``_balanced``);
* ``dt`` is not clamped (the published ``time_step_limit`` is (0, inf)).

Gradients are summed over blocks of rows, so that the published widths fit;
AdamW follows the configuration's hyperparameters and decays the leaves it
names.

``low`` selects the control: wherever the system computes in bfloat16, the
configuration's compute dtype, the control holds float8 e4m3: the embedded
tokens, the residual stream, norm outputs, projections and their weights,
the conv and gate activations, the SSD einsums' operands, the attention's
q, k, v, its weights and output, the experts' activations and the head's
operands.  The router's logits and choice stay float32 in both, as in the
system; AdamW stays float32 in both.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.models.mamba2 import _rmsnorm, _rounder, _segsum, key_for
from chipbench.models.mamba2 import leaf_norms, make_batches  # noqa: F401

ROW_BLOCK = 2   # rows of the batch per reference gradient block
Q_BLOCK = 512   # query rows per attention block of the reference
KINDS = ("ssd", "moe_only", "attn_only")


def check_config(cfg: dict) -> None:
    """Refuse a pattern with kinds this reference does not write out."""
    m = cfg["model"]
    bad = set(m["pattern"]) - set(KINDS)
    if bad:
        raise ValueError(f"{cfg['name']}: block kinds {sorted(bad)} are not "
                         f"in this reference")
    if m["n_layers"] % len(m["pattern"]):
        raise ValueError(f"{cfg['name']}: n_layers is not whole periods")


def dims(m: dict) -> dict:
    D, P, G, N = m["d_model"], m["ssm_head_dim"], m["ssm_groups"], \
        m["ssm_state"]
    Di = m["ssm_heads"] * P
    return {"D": D, "Di": Di, "H": m["ssm_heads"], "P": P, "N": N, "G": G,
            "C": Di + 2 * G * N, "F": 2 * Di + 2 * G * N + m["ssm_heads"],
            "W": m["conv_width"], "V": m["vocab_size"],
            "R": m["n_layers"] // len(m["pattern"]), "E": m["n_experts"],
            "Eh": m["moe_experts_held"], "Fe": m["moe_d_ff"],
            "Fs": m["moe_shared_d_ff"], "Hq": m["n_heads"],
            "Hkv": m["n_kv_heads"], "hd": m["head_dim"]}


def init_params(m: dict, seed: int):
    """The weights for ``seed``, float32, in one jitted call."""
    d = dims(m)
    R, D = d["R"], d["D"]

    def make(key):
        ks = iter(jax.random.split(key, 16 * len(m["pattern"]) + 4))

        def normal(shape, scale):
            return jax.random.normal(next(ks), shape, jnp.float32) * scale

        def proj(shape):        # (R, fan_in, fan_out) or (R, X, fan_in, ..)
            return normal(shape, 1.0 / math.sqrt(shape[-2]))

        def block(kind):
            if kind == "ssd":
                H = d["H"]
                # dt drawn log-uniform in [1e-3, 1e-1] (the published
                # time_step_min/max), dt_bias its inverse softplus
                u = jax.random.uniform(next(ks), (R, H), jnp.float32)
                dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3))
                             + math.log(1e-3))
                mixer = {
                    "w_in": proj((R, D, d["F"])),
                    "conv_w": normal((R, d["W"], d["C"]), 0.3),
                    "conv_b": normal((R, d["C"]), 0.1),
                    "A_log": jnp.broadcast_to(
                        jnp.log(jnp.linspace(1.0, 16.0, H)), (R, H)),
                    "D_skip": jnp.ones((R, H), jnp.float32),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "norm": jnp.zeros((R, d["Di"]), jnp.float32),
                    "w_out": proj((R, d["Di"], D)),
                }
                return {"ln1": jnp.zeros((R, D), jnp.float32), "ssd": mixer}
            if kind == "moe_only":
                Eh = d["Eh"]
                mixer = {
                    "router": proj((R, D, d["E"])),
                    "router_bias": jnp.zeros((R, d["E"]), jnp.float32),
                    "w_up": proj((R, Eh, D, d["Fe"])),
                    "w_down": proj((R, Eh, d["Fe"], D)),
                    "shared": {"w_up": proj((R, D, d["Fs"])),
                               "w_down": proj((R, d["Fs"], D))},
                }
                return {"ln1": jnp.zeros((R, D), jnp.float32), "moe": mixer}
            hq, hk = d["Hq"] * d["hd"], d["Hkv"] * d["hd"]
            return {"ln1": jnp.zeros((R, D), jnp.float32),
                    "attn": {"wq": proj((R, D, hq)), "wk": proj((R, D, hk)),
                             "wv": proj((R, D, hk)), "wo": proj((R, hq, D))}}

        return {"embed": normal((d["V"], D), 0.02),
                "final_norm": jnp.zeros((D,), jnp.float32),
                "head": normal((D, d["V"]), 1.0 / math.sqrt(D)),
                "stack": {"groups": [block(k) for k in m["pattern"]],
                          "rem": []}}

    params = jax.jit(make)(key_for(seed, 0))
    tokens = jax.random.randint(key_for(seed, 2), (1, BALANCE_SEQ), 0,
                                d["V"], jnp.int32)
    return jax.jit(lambda p, t: _balanced(m, d, p, t))(params, tokens)


BALANCE_SEQ = 4096    # tokens the correction bias is balanced on
BALANCE_STEPS = 400   # its sign steps, each 0.98 of the one before


def _balance(scores, k: int):
    """A correction bias (E,) under which the top-``k`` choices over
    ``scores`` (T, E) fall evenly on the experts: sign steps against each
    expert's excess load, as aux-loss-free balancing (DeepSeek-V3, whose
    ``e_score_correction_bias`` Nemotron-H keeps) runs in training."""
    T, E = scores.shape

    def step(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        return b - 0.05 * 0.98 ** i * jnp.sign(load - T * k / E)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((E,), jnp.float32))


def _balanced(m, d, params, tokens):
    """``params`` with each MoE layer's correction bias balanced on the
    router inputs that ``tokens`` give, layer by layer through the
    reference's own float32 layers: a trained model's state, in which every
    expert, held or not, gets its even share of the (token, slot) pairs."""
    out = jax.tree.map(lambda a: a, params)
    biases = {i: [] for i, kind in enumerate(m["pattern"])
              if kind == "moe_only"}
    x = params["embed"][tokens]
    for r in range(d["R"]):
        for i, kind in enumerate(m["pattern"]):
            p = jax.tree.map(lambda a: a[r], params["stack"]["groups"][i])
            if kind == "moe_only":
                h = _rmsnorm(x, p["ln1"], m["norm_eps"])
                scores = jax.nn.sigmoid(_mm("bsd,de->bse", h,
                                            p["moe"]["router"]))
                p["moe"]["router_bias"] = _balance(
                    scores.reshape(-1, d["E"]), m["moe_top_k"])
                biases[i].append(p["moe"]["router_bias"])
            x = x + MIXERS[kind](m, d, lambda v: v, x, p)
    for i, bs in biases.items():
        out["stack"]["groups"][i]["moe"]["router_bias"] = jnp.stack(bs)
    return out


# ------------------------------------------------------------- the reference
def _mm(spec, *ops):
    return jnp.einsum(spec, *ops, precision=jax.lax.Precision.HIGHEST)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _ssd(X, A, B, C, Q, q):
    """The paper's minimal chunked SSD with grouped B and C.  X (b, l, h,
    p) already scaled by dt; A (b, l, h) = dt * a; B, C (b, l, g, n), group
    g read by heads [g h/g, (g+1) h/g).  ``q`` rounds the operands of every
    einsum (the control's float8)."""
    b, l, h, p = X.shape
    g, n = B.shape[2], B.shape[3]
    j, c = h // g, l // Q
    X = X.reshape(b, c, Q, g, j, p)
    B = B.reshape(b, c, Q, g, n)
    C = C.reshape(b, c, Q, g, n)
    A = jnp.moveaxis(A.reshape(b, c, Q, g, j), 2, -1)      # (b, c, g, j, Q)
    A = jnp.moveaxis(A, 1, 3)                               # (b, g, j, c, Q)
    A_cum = jnp.cumsum(A, -1)

    def es(spec, *ops):
        return _mm(spec, *map(q, ops))

    Lm = jnp.exp(_segsum(A))                            # (b, g, j, c, Q, Q)
    y_diag = es("bclgn,bcsgn,bgjcls,bcsgjp->bclgjp", C, B, Lm, X)
    decay = jnp.exp(A_cum[..., -1:] - A_cum)                # (b, g, j, c, Q)
    states = es("bclgn,bgjcl,bclgjp->bcgjpn", B, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk = jnp.exp(_segsum(jnp.pad(A_cum[..., -1],
                                    ((0, 0), (0, 0), (0, 0), (1, 0)))))
    states = es("bgjzc,bcgjpn->bzgjpn", chunk, states)[:, :-1]
    y_off = es("bclgn,bcgjpn,bgjcl->bclgjp", C, states, jnp.exp(A_cum))
    return (y_diag + y_off).reshape(b, l, h, p)


def _mamba(m, d, q, x, p):
    s = p["ssd"]
    Bsz, S, _ = x.shape
    Di, N, G, W = d["Di"], d["N"], d["G"], d["W"]
    eps = m["norm_eps"]
    h = q(_rmsnorm(x, p["ln1"], eps))
    zx = q(_mm("bsd,df->bsf", h, q(s["w_in"])))
    z = zx[..., :Di]
    xbc = zx[..., Di:2 * Di + 2 * G * N]
    dt = zx[..., 2 * Di + 2 * G * N:]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    w = q(s["conv_w"])
    conv = sum(pad[:, i:i + S] * w[i] for i in range(W))
    conv = q(jax.nn.silu(q(conv) + q(s["conv_b"])))
    xs = conv[..., :Di].reshape(Bsz, S, d["H"], d["P"])
    Bm = conv[..., Di:Di + G * N].reshape(Bsz, S, G, N)
    Cm = conv[..., Di + G * N:].reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt + s["dt_bias"])
    a = -jnp.exp(s["A_log"]) * dt
    y = _ssd(xs * dt[..., None], a, Bm, Cm, m["ssm_chunk"], q)
    y = q((y + s["D_skip"][:, None] * xs).reshape(Bsz, S, Di))
    g = q(y * jax.nn.silu(z)).reshape(Bsz, S, G, Di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    y = q(g.reshape(Bsz, S, Di) * (1.0 + s["norm"]))
    return q(_mm("bsi,id->bsd", y, q(s["w_out"])))


def route(m, d, h, p):
    """Expert ids (b, s, k) and weights (b, s, k) of the sigmoid router."""
    logits = _mm("bsd,de->bse", h, p["router"])
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"], m["moe_top_k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * m["moe_routed_scale"]


def _moe(m, d, q, x, p, first=0):
    """The MoE output of the held experts ``[first, first + Eh)`` and the
    shared expert."""
    s = p["moe"]
    h = q(_rmsnorm(x, p["ln1"], m["norm_eps"]))
    idx, w = route(m, d, h, s)
    y = q(_mm("bsf,fd->bsd", q(_relu2(_mm("bsd,df->bsf", h,
                                           q(s["shared"]["w_up"])))),
              q(s["shared"]["w_down"])))
    for e in range(d["Eh"]):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)   # (b, s)
        up = q(_relu2(_mm("bsd,df->bsf", h, q(s["w_up"][e]))))
        y = y + w_e[..., None] * q(_mm("bsf,fd->bsd", up, q(s["w_down"][e])))
    return q(y)


def _attention(m, d, q, x, p):
    a = p["attn"]
    Bsz, S, _ = x.shape
    Hq, Hkv, hd = d["Hq"], d["Hkv"], d["hd"]
    h = q(_rmsnorm(x, p["ln1"], m["norm_eps"]))
    qh = q(_mm("bsd,dk->bsk", h, q(a["wq"]))).reshape(Bsz, S, Hq, hd)
    kh = q(_mm("bsd,dk->bsk", h, q(a["wk"]))).reshape(Bsz, S, Hkv, hd)
    vh = q(_mm("bsd,dk->bsk", h, q(a["wv"]))).reshape(Bsz, S, Hkv, hd)
    kh = jnp.repeat(kh, Hq // Hkv, axis=2)
    vh = jnp.repeat(vh, Hq // Hkv, axis=2)
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qh, i * qb, qb, 1)
        sc = _mm("bqhe,bshe->bhqs", qi, kh) / math.sqrt(hd)
        rows = i * qb + jnp.arange(qb)
        sc = jnp.where(rows[:, None] >= jnp.arange(S)[None, :], sc, -jnp.inf)
        return q(_mm("bhqs,bshe->bqhe", q(jax.nn.softmax(sc, -1)), vh))

    o = jax.lax.map(block, jnp.arange(S // qb))       # (nq, b, qb, h, e)
    o = jnp.moveaxis(o, 0, 1).reshape(Bsz, S, Hq * hd)
    return q(_mm("bsk,kd->bsd", o, q(a["wo"])))


MIXERS = {"ssd": _mamba, "moe_only": _moe, "attn_only": _attention}


def _nll_sum(m: dict, low: bool):
    q = _rounder(low)
    d = dims(m)

    def layer(kind):
        return jax.checkpoint(
            lambda x, p: q(x + MIXERS[kind](m, d, q, x, p)))

    def f(params, tokens, targets):
        x = q(params["embed"][tokens])
        for r in range(d["R"]):
            for kind, g in zip(m["pattern"], params["stack"]["groups"]):
                x = layer(kind)(x, jax.tree.map(lambda a: a[r], g))
        x = q(_rmsnorm(x, params["final_norm"], m["norm_eps"]))
        logits = _mm("bsd,dv->bsv", x, q(params["head"]))
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
    (or to ``decayed``).  The correction bias has no gradient and stays."""
    grad_sum = jax.jit(jax.value_and_grad(_nll_sum(m, low)))
    return adamw_steps(grad_sum, opt, params, batches, decayed)


def adamw_steps(grad_sum, opt: dict, params, batches: list,
                decayed=None) -> dict:
    """AdamW on the mean of ``grad_sum``'s summed loss and gradients over
    row blocks (``mamba2.reference_steps``'s loop, for any model).  The
    gradient sum and the update take their running arrays in place
    (donated) and the starting weights wait on the host, so that weights,
    moments and gradient (8.4 GB at published widths) and one block's
    temporaries fit one chip."""
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

    @partial(jax.jit, donate_argnums=1)
    def accumulate(total, grads, params, tok, tgt):
        s, g = grad_sum(params, tok, tgt)
        return total + s, jax.tree.map(jnp.add, grads, g)

    @partial(jax.jit, donate_argnums=(0, 2, 3))
    def adamw(p, g, mo, v, t, lr_t):
        mo = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, mo, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)

        def upd(p_, m_, v_, wd_):
            step = m_ / (1 - b1 ** t) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
            return p_ - lr_t * (step + wd_ * p_)

        return jax.tree.map(upd, p, mo, v, rate), mo, v

    p0 = jax.device_get(params)
    mo = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for t, batch in enumerate(batches, start=1):
        tok, tgt = batch["tokens"], batch["targets"]
        n = tok.shape[0] * tok.shape[1]
        total = jnp.zeros((), jnp.float32)
        grads = jax.tree.map(jnp.zeros_like, params)
        for r in range(0, tok.shape[0], ROW_BLOCK):
            total, grads = accumulate(total, grads, params,
                                      tok[r:r + ROW_BLOCK],
                                      tgt[r:r + ROW_BLOCK])
        grads = jax.tree.map(lambda x: x / n, grads)
        losses.append(float(total) / n)
        if g1 is None:
            g1 = leaf_norms(grads)
        params, mo, v = adamw(params, grads, mo, v, jnp.float32(t),
                              jnp.float32(lr(t)))
        del grads
    del mo, v
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, jax.device_put(p0)))
    return {"losses": losses, "grad1": g1, "delta": delta}
