"""Public wrapper: platform dispatch + autotuned blocking for flash attn."""
import functools
import jax

from ..runtime import resolve_impl
from ..tuning import get_tuner
from .kernel import flash_attention_kernel
from .ref import attention_ref

DEFAULT_BLOCK = 512


# the (B,S,H,D)->(B,H,S,D) layout transposes live inside the jitted calls
# so eager invocations (e.g. the benchmark timing path) still get them
# fused instead of paying four materialised copies per call


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale"))
def _ref_call(q, k, v, *, causal, window, scale):
    out = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal, window=window,
                        scale=scale)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret",
                                             "scale"))
def _kernel_call(q, k, v, *, causal, window, q_block, kv_block, interpret,
                 scale):
    out = flash_attention_kernel(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, interpret=interpret, scale=scale)
    return out.transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal=True, window=None, impl="auto",
                    q_block=None, kv_block=None, scale=None):
    """q: (B, S, H, D); k/v: (B, T, KV, D) — model layout; returns same.

    impl: auto (kernel on TPU, interpret elsewhere) | kernel | interpret | ref
    Unset block sizes come from the autotune cache, else the 512 default.
    ``scale``: the softmax scale (None: 1 / sqrt(D)).
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return _ref_call(q, k, v, causal=causal, window=window, scale=scale)
    if q_block is None or kv_block is None:
        cfg = get_tuner().lookup("flash_attention", q.shape, q.dtype,
                                 impl=impl) or {}
        q_block = q_block or cfg.get("q_block", DEFAULT_BLOCK)
        kv_block = kv_block or cfg.get("kv_block", DEFAULT_BLOCK)
    return _kernel_call(q, k, v, causal=causal, window=window,
                        q_block=q_block, kv_block=kv_block,
                        interpret=(impl == "interpret"), scale=scale)
