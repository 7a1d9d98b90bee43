"""Granite 4.0-H weights from a seed, and the plain float32 reference
forward.

Weights.  :func:`program_params` draws every weight on the device in one
jitted call, in the type the configuration serves (``torch_dtype``), laid
out as the system under test takes them: the layers fall into periods of
``layer_types`` (the shortest repeating run, 10 layers: 5 Mamba-2, 1
attention, 4 Mamba-2), each position of the period one tree stacked over
the periods, leftover layers unstacked in ``tail``.  :func:`layer_weights`
draws one layer's weights from the same seed, bit for bit the same values,
upcast to float32 for the reference.  Values: projections and the
depthwise conv truncated normal (2 sigma) at ``1/sqrt(fan_in)``, norm
scales, conv biases and ``D`` near 1 or 0 by ``NORM_STD``/``BIAS_STD``,
Mamba-2's own initialisation of ``A_log`` (log of U(1, 16)) and of
``dt_bias`` (softplus inverse of a step log-uniform in [1e-3, 1e-1]), and
the tied embedding N(0, ``EMBED_STD``) with zero rows past the published
vocabulary.  ``EMBED_STD`` is what keeps the served-token check sharp.
The embedding multiplier (12) leaves the current token's own embedding in
the residual stream, and the tied head scores that token ~12 sigma^2 d
above its own dot product with the rest, against a spread of ~sigma |h|
for every other token.  At Qwen2's 0.02 and d 2048 the copy wins at
every position: the chip served only copies, and even the fp8 control
read a gap of 0.  At 0.0025 no position is a copy and the fp8 control
lies ~8x above the bf16 program (CPU, d 256 and 512 at the same
sigma sqrt(d), 40 layers).

Reference.  :func:`forward_logits` is Granite 4.0-H as published (HF
``GraniteMoeHybridForCausalLM`` with no experts): RMSNorm at
``rms_norm_eps``; embedding times ``embedding_multiplier``; per layer a
mixer and a SwiGLU MLP (``shared_intermediate_size``), each on a pre-norm
branch scaled by ``residual_multiplier``.  The Mamba-2 mixer is the plain
per-token recurrence (a ``lax.scan`` over tokens; no chunks): causal
depthwise conv with bias over x, B, C, SiLU, ``dt = softplus(dt +
dt_bias)``, ``h = exp(dt A) h + dt B x``, ``y = C h + D x``, the gated
RMSNorm over the whole of d_inner (one group), out projection.  Attention
is NoPE GQA with softmax scale ``attention_multiplier``, no biases.  The
tied head's logits are divided by ``logits_scaling``.  All in float32
under ``default_matmul_precision("highest")``, one sequence at a time and
layer by layer, so that it fits beside nothing else.  With
``quant="fp8"`` every 2-D weight is rounded to float8 e4m3 with a
per-output-channel scale and the layer computes in bfloat16 (the
recurrence's state stays float32, as the program keeps it): the control,
one precision step below the bfloat16 the configuration serves."""
from __future__ import annotations

import functools
import math

import numpy as np

from bench.reference.qwen2 import (_jit, _quant_fp8, _rms, _seed_key,
                                   served_gaps)

__all__ = ["program_params", "forward_logits", "served_gaps",
           "check_config", "layer_weights"]

EMBED_STD = 0.0025
BIAS_STD = 0.1
NORM_STD = 0.1
# the program's layer kinds for the configuration's layer types
KIND = {"mamba": "ssm", "attention": "dense"}

# leaf name -> (shape builder, kind); kind: "w" fan-in scaled, "b" bias
# (0 + noise), "n" norm scale (1 + noise), "A" A_log, "dt" dt_bias.
# Order fixes each leaf's key.
MLP = (
    ("ln2", lambda c: (c["d"],), "n"),
    ("gate", lambda c: (c["d"], c["ff"]), "w"),
    ("up", lambda c: (c["d"], c["ff"]), "w"),
    ("down", lambda c: (c["ff"], c["d"]), "w"),
)
LAYOUT = {
    "mamba": (
        ("ln1", lambda c: (c["d"],), "n"),
        ("in_proj", lambda c: (c["d"], 2 * c["di"] + 2 * c["N"] + c["nh"]),
         "w"),
        ("conv_w", lambda c: (c["W"], c["conv"]), "w"),
        ("conv_b", lambda c: (c["conv"],), "b"),
        ("A_log", lambda c: (c["nh"],), "A"),
        ("D", lambda c: (c["nh"],), "n"),
        ("dt_bias", lambda c: (c["nh"],), "dt"),
        ("norm", lambda c: (c["di"],), "n"),
        ("out_proj", lambda c: (c["di"], c["d"]), "w"),
    ) + MLP,
    "attention": (
        ("ln1", lambda c: (c["d"],), "n"),
        ("q", lambda c: (c["d"], c["H"] * c["hd"]), "w"),
        ("k", lambda c: (c["d"], c["KV"] * c["hd"]), "w"),
        ("v", lambda c: (c["d"], c["KV"] * c["hd"]), "w"),
        ("o", lambda c: (c["H"] * c["hd"], c["d"]), "w"),
    ) + MLP,
}


def period(types) -> int:
    """The shortest run of layer types that repeats over the depth."""
    L = len(types)
    return next(p for p in range(1, L + 1)
                if all(types[i] == types[i % p] for i in range(L)))


def dims(conf: dict) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    di = conf["mamba_expand"] * d
    N = conf["mamba_n_groups"] * conf["mamba_d_state"]
    return {"d": d, "H": H, "KV": conf["num_key_value_heads"],
            "hd": d // H, "ff": conf["shared_intermediate_size"],
            "L": conf["num_hidden_layers"], "V": conf["vocab_size"],
            "types": tuple(conf["layer_types"]),
            "di": di, "N": N, "nh": conf["mamba_n_heads"],
            "P": conf["mamba_d_head"], "W": conf["mamba_d_conv"],
            "conv": di + 2 * N,
            "eps": float(conf["rms_norm_eps"]),
            "emb_mult": float(conf["embedding_multiplier"]),
            "attn_mult": float(conf["attention_multiplier"]),
            "res_mult": float(conf["residual_multiplier"]),
            "logit_div": float(conf["logits_scaling"])}


def check_config(cfg, conf: dict) -> None:
    """Raise where the system's ``ModelConfig`` departs from what a
    Granite 4.0-H configuration states beyond the sizes every decoder
    has: the layer types, the four scalars and the norm epsilon, the
    Mamba-2 sizes, the MLP width, NoPE and the biases."""
    name = conf["program_arch"]
    types = conf["layer_types"]
    pat = tuple(cfg.layer_pattern)
    kinds = [pat[i % len(pat)] for i in range(cfg.n_layers)] if pat else []
    if kinds != [KIND[t] for t in types]:
        raise ValueError(f"{name}: layer kinds {kinds} but the published "
                         f"layer_types are {types}")
    d = conf["hidden_size"]
    for key, field, want in (
            ("embedding_multiplier", "embed_multiplier", None),
            ("attention_multiplier", "attn_scale", None),
            ("residual_multiplier", "residual_multiplier", None),
            ("logits_scaling", "logits_divisor", None),
            ("rms_norm_eps", "norm_eps", None),
            ("shared_intermediate_size", "d_ff", None),
            ("mamba_n_heads", "ssm_n_heads", None),
            ("mamba_d_head", "ssm_head_dim", None),
            ("mamba_d_state", "ssm_state", None),
            ("mamba_d_conv", "ssm_conv", None),
            ("mamba_expand", "ssm_expand", None),
            ("mamba_chunk_size", "ssm_chunk", None),
            ("position_embedding_type", "use_rope",
             conf["position_embedding_type"] != "nope"),
            ("attention_bias", "qkv_bias", None),
            ("hidden_act", "act", None)):
        got = getattr(cfg, field)
        want = conf[key] if want is None else want
        if got != want:
            raise ValueError(f"{name}: {field}={got!r} but the published "
                             f"{key} is {conf[key]!r}")
    # what the program has no switch for: one group of B and C, a conv
    # bias, no in/out projection bias, a gated MLP in every layer, no
    # experts, a head size of hidden / heads
    fixed = {"mamba_n_groups": 1, "mamba_conv_bias": True,
             "mamba_proj_bias": False, "num_local_experts": 0,
             "normalization_function": "rmsnorm"}
    for key, want in fixed.items():
        if conf[key] != want:
            raise ValueError(f"{name}: the program runs {key}={want!r}, the "
                             f"configuration states {conf[key]!r}")
    if not cfg.ssm_mlp or cfg.hd * cfg.n_heads != d:
        raise ValueError(f"{name}: needs an MLP after every mixer and a "
                         f"head size of hidden / heads")


def _leaf(jax, key, shape, kind, dtype):
    import jax.numpy as jnp
    if kind == "w":
        std = 1.0 / math.sqrt(shape[0]) / 0.87962566103423978
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape) * std
    elif kind == "b":
        x = jax.random.normal(key, shape) * BIAS_STD
    elif kind == "n":
        x = 1.0 + jax.random.normal(key, shape) * NORM_STD
    elif kind == "A":
        x = jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))
    else:
        step = jnp.exp(jax.random.uniform(key, shape, minval=math.log(1e-3),
                                          maxval=math.log(1e-1)))
        x = step + jnp.log(-jnp.expm1(-step))    # softplus^-1(step)
    return x.astype(dtype)


def _layer_leaves(jax, key, c, kind, dtype):
    return {name: _leaf(jax, jax.random.fold_in(key, i), shape(c), lk,
                        dtype)
            for i, (name, shape, lk) in enumerate(LAYOUT[kind])}


def _keys(jax, seed):
    k = _seed_key(jax, seed)
    return (jax.random.fold_in(k, 1), jax.random.fold_in(k, 2),
            jax.random.fold_in(k, 3))


def _table(jax, key, c, padded_vocab, dtype):
    import jax.numpy as jnp
    t = (jax.random.normal(key, (c["V"], c["d"])) * EMBED_STD).astype(dtype)
    if padded_vocab > c["V"]:
        t = jnp.concatenate(
            [t, jnp.zeros((padded_vocab - c["V"], c["d"]), dtype)])
    return t


def _block(kind, lv):
    """One layer's leaves in the program's block layout."""
    mlp = {n: {"w": lv[n]} for n in ("gate", "up", "down")}
    if kind == "attention":
        return {"ln1": {"scale": lv["ln1"]},
                "attn": {n: {"w": lv[n]} for n in ("q", "k", "v", "o")},
                "ln2": {"scale": lv["ln2"]}, "mlp": mlp}
    mixer = {n: lv[n] for n in ("conv_w", "conv_b", "A_log", "D", "dt_bias")}
    mixer.update(in_proj={"w": lv["in_proj"]}, out_proj={"w": lv["out_proj"]},
                 norm={"scale": lv["norm"]})
    return {"ln1": {"scale": lv["ln1"]}, "mixer": mixer,
            "ln2": {"scale": lv["ln2"]}, "mlp": mlp}


def program_params(jax, conf: dict, seed: int, *, padded_vocab: int,
                   dtype: str):
    """All weights, one jitted call on the default device, in ``dtype``,
    in the system's layout.  The seed enters as key arguments, so every
    seed runs the same compiled program."""
    import jax.numpy as jnp
    c = dims(conf)
    dt = jnp.dtype(dtype)
    p = period(c["types"])
    groups, rest = divmod(c["L"], p)

    def build(k_layers, k_table, k_norm):
        def stacked(i):
            kind = c["types"][i]
            lk = jax.vmap(lambda l: jax.random.fold_in(k_layers, l))(
                i + p * jnp.arange(groups))
            return _block(kind, jax.vmap(
                lambda k: _layer_leaves(jax, k, c, kind, dt))(lk))

        tail = [_block(c["types"][l], _layer_leaves(
            jax, jax.random.fold_in(k_layers, l), c, c["types"][l], dt))
            for l in range(groups * p, c["L"])]
        return {"embed": {"table": _table(jax, k_table, c, padded_vocab, dt)},
                "groups": [stacked(i) for i in range(p)] if groups else [],
                "tail": tail,
                "norm_f": {"scale": _leaf(jax, k_norm, (c["d"],), "n", dt)}}

    return jax.jit(build)(*_keys(jax, seed))


def layer_weights(jax, conf: dict, seed: int, layer: int, *,
                  served_dtype: str):
    """One layer's weights in float32, equal to the served values."""
    import jax.numpy as jnp
    c = dims(conf)
    dt = jnp.dtype(served_dtype)
    kind = c["types"][layer]

    def make():
        def build(k_layers, layer):
            leaves = _layer_leaves(jax, jax.random.fold_in(k_layers, layer),
                                   c, kind, dt)
            return {k: v.astype(jnp.float32) for k, v in leaves.items()}
        return build

    fn = _jit(jax, ("granite_layer", kind, _ckey(c), served_dtype), make)
    return fn(_keys(jax, seed)[0], layer)


def head_weights(jax, conf: dict, seed: int, *, served_dtype: str):
    """(embedding table over the published vocabulary, final norm scale)
    in float32, equal to the served values."""
    import jax.numpy as jnp
    c = dims(conf)
    dt = jnp.dtype(served_dtype)

    def make():
        def build(k_table, k_norm):
            return (_table(jax, k_table, c, c["V"], dt).astype(jnp.float32),
                    _leaf(jax, k_norm, (c["d"],), "n", dt).astype(
                        jnp.float32))
        return build

    fn = _jit(jax, ("granite_head", _ckey(c), served_dtype), make)
    _, k_table, k_norm = _keys(jax, seed)
    return fn(k_table, k_norm)


def _ckey(c: dict):
    return tuple(sorted(c.items()))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _weights(W, quant):
    """The layer's weights as the forward computes with them: float32, or
    for the control every 2-D weight rounded to float8 and the rest in
    bfloat16."""
    import jax.numpy as jnp
    if not quant:
        return W
    return {k: (_quant_fp8(v) if v.ndim == 2 else v.astype(jnp.bfloat16))
            for k, v in W.items()}


def _dot(x, w):
    import jax.numpy as jnp
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32).astype(w.dtype)


def _mlp(h, Wm, c):
    import jax
    x = _rms(h, Wm["ln2"], c["eps"])
    m = jax.nn.silu(_dot(x, Wm["gate"])) * _dot(x, Wm["up"])
    return h + c["res_mult"] * _dot(m, Wm["down"]).astype(h.dtype)


def _mamba(h, W, c, quant):
    """One Mamba-2 layer over one sequence ``h`` (S, d): the mixer as the
    per-token recurrence, then the MLP."""
    import jax
    import jax.numpy as jnp
    Wm = _weights(W, quant)
    S = h.shape[0]
    di, N, nh, P, Wc = c["di"], c["N"], c["nh"], c["P"], c["W"]
    proj = _dot(_rms(h, Wm["ln1"], c["eps"]), Wm["in_proj"])
    z, xbc, dt = jnp.split(proj.astype(jnp.float32), [di, 2 * di + 2 * N],
                           axis=-1)
    # causal depthwise conv over x, B, C: tap i reads position t - (W-1) + i
    pad = jnp.pad(xbc, ((Wc - 1, 0), (0, 0)))
    conv_w = Wm["conv_w"].astype(jnp.float32)
    xbc = sum(pad[i:i + S] * conv_w[i] for i in range(Wc)) \
        + Wm["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    x, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    x = x.reshape(S, nh, P)
    dt = jax.nn.softplus(dt + Wm["dt_bias"].astype(jnp.float32))   # (S, nh)
    A = -jnp.exp(Wm["A_log"].astype(jnp.float32))                 # (nh,)

    def step(state, t):
        xt, bt, ct, dtt = t                 # (nh,P) (N,) (N,) (nh,)
        state = (state * jnp.exp(dtt * A)[:, None, None]
                 + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return state, state @ ct                                   # (nh, P)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N), jnp.float32),
                        (x, Bm, Cm, dt))
    y = y + x * Wm["D"].astype(jnp.float32)[:, None]
    g = y.reshape(S, di) * jax.nn.silu(z)
    g = _rms(g, Wm["norm"].astype(jnp.float32), c["eps"])
    out = _dot(g, Wm["out_proj"])
    h = h + c["res_mult"] * out.astype(h.dtype)
    return _mlp(h, Wm, c)


def _attention(h, W, c, q_block: int, quant):
    """One attention layer over one sequence ``h`` (S, d): NoPE GQA at the
    configuration's softmax scale, then the MLP."""
    import jax
    import jax.numpy as jnp
    Wm = _weights(W, quant)
    S = h.shape[0]
    H, KV, hd = c["H"], c["KV"], c["hd"]
    x = _rms(h, Wm["ln1"], c["eps"])
    q = _dot(x, Wm["q"]).reshape(S, H, hd)
    k = _dot(x, Wm["k"]).reshape(S, KV, hd)
    v = _dot(x, Wm["v"]).reshape(S, KV, hd)
    G = H // KV
    pos = jnp.arange(S)
    outs = []
    for lo in range(0, S, q_block):          # causal: keys up to block end
        hi = min(lo + q_block, S)
        qb = q[lo:hi].reshape(hi - lo, KV, G, hd).astype(jnp.float32)
        kb, vb = k[:hi].astype(jnp.float32), v[:hi].astype(jnp.float32)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, kb) * c["attn_mult"]
        mask = pos[lo:hi][:, None] >= pos[:hi][None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p, vb)
        outs.append(o.reshape(hi - lo, H * hd).astype(q.dtype))
    out = _dot(jnp.concatenate(outs, axis=0), Wm["o"])
    h = h + c["res_mult"] * out.astype(h.dtype)
    return _mlp(h, Wm, c)


def _head(h, rows, norm_f, table, c, quant):
    """Logits at ``rows`` of ``h`` over the published vocabulary."""
    import jax.numpy as jnp
    x = _rms(h[rows], norm_f, c["eps"])
    head = _quant_fp8(table.T) if quant else table.T
    logits = jnp.dot(x.astype(head.dtype), head,
                     preferred_element_type=jnp.float32)
    return logits / c["logit_div"]


def forward_logits(jax, conf: dict, seed: int, seqs, rows, *,
                   served_dtype: str, quant: str | None = None,
                   bucket: int = 1024, q_block: int = 512,
                   row_block: int = 128):
    """Logits over the published vocabulary of each sequence in ``seqs``
    (int token arrays) at the positions ``rows[i]`` (each position's
    logits predict the next token).  Sequences are padded at the end to a
    multiple of ``bucket`` (every layer is causal: padding changes nothing
    before it), rows to a multiple of ``row_block``, so a few program
    shapes serve every sample.  Returns a list of float32 numpy arrays
    (len(rows[i]), V)."""
    import jax.numpy as jnp
    c = dims(conf)
    ckey = _ckey(c)
    with jax.default_matmul_precision("highest"):
        table, norm_f = head_weights(jax, conf, seed,
                                     served_dtype=served_dtype)
        dt = jnp.bfloat16 if quant else jnp.float32
        embed = _jit(jax, ("granite_embed", dt, c["emb_mult"]),
                     lambda: lambda t, x: (jnp.take(t, x, axis=0)
                                           * c["emb_mult"]).astype(dt))
        hs = []
        for s in seqs:
            S = -(-len(s) // bucket) * bucket
            toks = np.zeros(S, np.int32)
            toks[:len(s)] = s
            hs.append(embed(table, toks))
        steps = {
            "mamba": _jit(jax, ("granite_mamba", ckey, quant),
                          lambda: functools.partial(_mamba, c=c,
                                                    quant=quant)),
            "attention": _jit(jax, ("granite_attn", ckey, q_block, quant),
                              lambda: functools.partial(
                                  _attention, c=c, q_block=q_block,
                                  quant=quant))}
        for layer in range(c["L"]):
            W = layer_weights(jax, conf, seed, layer,
                              served_dtype=served_dtype)
            step = steps[c["types"][layer]]
            hs = [step(h, W) for h in hs]
            del W
        head = _jit(jax, ("granite_head_logits", ckey, quant),
                    lambda: functools.partial(_head, c=c, quant=quant))
        out = []
        for h, r in zip(hs, rows):
            r = np.asarray(r, np.int32)
            n = -(-len(r) // row_block) * row_block
            rp = np.zeros(n, np.int32)
            rp[:len(r)] = r
            out.append(np.asarray(head(h, rp, norm_f, table),
                                  np.float32)[:len(r)])
    return out
