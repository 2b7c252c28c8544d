"""Public wrappers around the Pallas kernels: the composed SSD scan that
takes the model layer's arguments, beside the kernels' own entry points
(``flash_attention``, differentiable, is the attention kernel pair's).
``interpret=True`` runs a kernel in the Pallas interpreter (the CPU tests);
otherwise it lowers through Mosaic for the TPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ssd
from .flash_attention import flash_attention
from .rglru import rglru_scan


def ssd_scan(x, dtv, A, Bm, Cm, D, h0=None, chunk: int = 256,
             interpret: bool = False):
    """The SSD layer's chunked scan and its skip, ``SSD(x) + D x``, through
    the Pallas kernel pair.

    x: (B, S, H*P), read as bfloat16; dtv: (B, S, H) (softplus'd); A: (H,)
    log-decay rates, negative, as ``models.layers._ssd_chunked`` (the
    oracle) takes them; Bm, Cm: (B, S, N), shared by the heads, or (B, S,
    G, N), one for each run of H / G heads; D: (H,); h0: (B, H, P, N) or
    None.  The shapes must satisfy ``ssd.ssd_tiles``.  Returns (y (B, S,
    H*P) float32, h_last (B, H, P, N) float32), differentiable in every
    array argument.  The within-chunk cumulative sum of ``dt * A`` is a
    float32 product with a 0/1 triangle at ``Precision.HIGHEST``: exact
    products, summed in float32, as the MXU does it faster than XLA's
    windowed cumulative sum.
    """
    Bsz, S, HP = x.shape
    H, N = dtv.shape[-1], Bm.shape[-1]
    Bm, Cm = Bm.reshape(Bsz, S, -1), Cm.reshape(Bsz, S, -1)  # group-major
    Q = min(chunk, S)
    nc = S // Q
    assert nc * Q == S, "sequence must be divisible by the chunk"
    f32 = jnp.float32
    dA = (dtv.astype(f32) * A).reshape(Bsz, nc, Q, H)
    tri = jnp.tril(jnp.ones((Q, Q), f32))                   # k <= q
    cum = jnp.einsum("qk,bckh->bcqh", tri, dA,
                     precision=jax.lax.Precision.HIGHEST).reshape(Bsz, S, H)
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, HP // H, N), f32)
    return ssd.ssd_scan(x, dtv.astype(f32), cum, Bm.astype(f32),
                        Cm.astype(f32), D.astype(f32), h0.astype(f32), Q,
                        interpret)


__all__ = ["flash_attention", "rglru_scan", "ssd_scan"]
