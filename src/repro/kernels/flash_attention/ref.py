"""Pure-jnp oracle for the flash attention kernel."""
import math
import jax.numpy as jnp
import jax

NEG_INF = -2.3819763e38


def attention_ref(q, k, v, *, causal=True, window=None, scale=None):
    """q: (B,H,S,D); k/v: (B,KV,T,D) -> (B,H,S,D). fp32 internally;
    ``scale`` None is 1 / sqrt(D)."""
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s / math.sqrt(D) if scale is None else s * scale
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(T)[None, :]
    ok = jnp.ones((S, T), bool)
    if causal:
        ok = ok & (qp >= kp)
    if window is not None:
        ok = ok & (qp - kp < window)
    s = jnp.where(ok[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", w, v.astype(jnp.float32)).astype(q.dtype)
