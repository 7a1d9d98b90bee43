"""Qwen2 weights from a seed, and the plain float32 reference forward.

Weights.  :func:`program_params` draws every weight on the device in one
jitted call, in the type the configuration serves (``torch_dtype``), laid
out as the system under test takes them (per-layer leaves stacked on a
leading layer axis; see ``LAYOUT``).  :func:`layer_weights` draws one
layer's weights from the same seed, bit for bit the same values, upcast to
float32 for the reference.  Values: truncated normal at ``1/sqrt(fan_in)``
for projections, N(0, ``initializer_range``) for the tied embedding, as the
published config initialises it (rows past the published vocabulary are
zero, as a published checkpoint loaded into a padded table leaves them),
small random q/k/v biases and norm scales near 1.  Two choices keep the
served-token check sharp.  At an embedding scale of 1 the residual stream
would carry the current token's own embedding far above all the layers
add, and the tied unembedding would make every greedy token a copy of the
one before it (a check of such tokens passes with attention broken).  And
q and k stay at the plain fan-in scale: at twice it, attention turns sharp
and the 28 layers chaotic, so bf16 rounding alone moves the greedy token at
most positions and the bf16 program reads within 1.5x of its fp8 control
(my chip runs, PR 12), where at the plain scale the two lie 10x apart.

Reference.  :func:`forward_logits` is Qwen2 as published (arXiv:2407.10671;
HF ``Qwen2ForCausalLM``): RMSNorm (eps from the configuration), GQA with
q/k/v biases, rotate-half RoPE at ``rope_theta``, SwiGLU MLP, tied
embedding, in float32 under ``default_matmul_precision("highest")``, one
sequence at a time and layer by layer, so it fits beside nothing else.
It returns the logits over the published vocabulary at the positions
asked for.  With ``quant="fp8"`` every projection weight is rounded to
float8 e4m3 with a per-output-channel scale and the layer computes in
bfloat16: the control, one precision step below the bfloat16 the
configuration serves."""
from __future__ import annotations

import math

import numpy as np

# leaf name -> (shape builder, kind); kind: "w" fan-in scaled, "b" bias,
# "n" norm scale.  Order fixes each leaf's key.
LAYOUT = (
    ("ln1", lambda c: (c["d"],), "n"),
    ("q.w", lambda c: (c["d"], c["H"] * c["hd"]), "w"),
    ("q.b", lambda c: (c["H"] * c["hd"],), "b"),
    ("k.w", lambda c: (c["d"], c["KV"] * c["hd"]), "w"),
    ("k.b", lambda c: (c["KV"] * c["hd"],), "b"),
    ("v.w", lambda c: (c["d"], c["KV"] * c["hd"]), "w"),
    ("v.b", lambda c: (c["KV"] * c["hd"],), "b"),
    ("o.w", lambda c: (c["H"] * c["hd"], c["d"]), "w"),
    ("ln2", lambda c: (c["d"],), "n"),
    ("gate", lambda c: (c["d"], c["ff"]), "w"),
    ("up", lambda c: (c["d"], c["ff"]), "w"),
    ("down", lambda c: (c["ff"], c["d"]), "w"),
)
BIAS_STD = 0.1
NORM_STD = 0.1


def dims(conf: dict) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return {"d": d, "H": H, "KV": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or d // H,
            "ff": conf["intermediate_size"], "L": conf["num_hidden_layers"],
            "V": conf["vocab_size"], "theta": float(conf["rope_theta"]),
            "eps": float(conf["rms_norm_eps"]),
            "embed_std": float(conf["initializer_range"])}


def check_config(cfg, conf: dict) -> None:
    """Raise where the system's ``ModelConfig`` departs from what a Qwen2
    configuration states beyond the sizes every decoder has: the MLP
    width, the RoPE base, q/k/v biases and a head size of hidden / heads."""
    for key, field in (("intermediate_size", "d_ff"),
                       ("rope_theta", "rope_theta")):
        if getattr(cfg, field) != conf[key]:
            raise ValueError(f"{conf['program_arch']}: {field}="
                             f"{getattr(cfg, field)!r} but the published "
                             f"{key} is {conf[key]!r}")
    if not cfg.qkv_bias or cfg.hd * cfg.n_heads != conf["hidden_size"]:
        raise ValueError("qwen2 needs q/k/v biases and hidden/heads head size")


def _seed_key(jax, seed: int):
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(jax, key, shape, kind, dtype):
    import jax.numpy as jnp
    if kind == "w":
        std = 1.0 / math.sqrt(shape[0]) / 0.87962566103423978
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape) * std
    elif kind == "b":
        x = jax.random.normal(key, shape) * BIAS_STD
    else:
        x = 1.0 + jax.random.normal(key, shape) * NORM_STD
    return x.astype(dtype)


def _layer_leaves(jax, key, c, dtype):
    return {name: _leaf(jax, jax.random.fold_in(key, i), shape(c), kind,
                        dtype)
            for i, (name, shape, kind) in enumerate(LAYOUT)}


def _keys(jax, seed):
    k = _seed_key(jax, seed)
    return (jax.random.fold_in(k, 1), jax.random.fold_in(k, 2),
            jax.random.fold_in(k, 3))


def _table(jax, key, c, padded_vocab, dtype):
    import jax.numpy as jnp
    t = (jax.random.normal(key, (c["V"], c["d"])) * c["embed_std"]).astype(
        dtype)
    if padded_vocab > c["V"]:
        t = jnp.concatenate(
            [t, jnp.zeros((padded_vocab - c["V"], c["d"]), dtype)])
    return t


def program_params(jax, conf: dict, seed: int, *, padded_vocab: int,
                   dtype: str):
    """All weights, one jitted call on the default device, in ``dtype``,
    in the system's layout.  The seed enters as key arguments, so every
    seed runs the same compiled program."""
    import jax.numpy as jnp
    c = dims(conf)
    dt = jnp.dtype(dtype)

    def build(k_layers, k_table, k_norm):
        lk = jax.vmap(lambda l: jax.random.fold_in(k_layers, l))(
            jnp.arange(c["L"]))
        leaves = jax.vmap(lambda k: _layer_leaves(jax, k, c, dt))(lk)
        block = {
            "ln1": {"scale": leaves["ln1"]},
            "attn": {n: {"w": leaves[f"{n}.w"], **(
                {"b": leaves[f"{n}.b"]} if f"{n}.b" in leaves else {})}
                for n in ("q", "k", "v", "o")},
            "ln2": {"scale": leaves["ln2"]},
            "mlp": {n: {"w": leaves[n]} for n in ("gate", "up", "down")},
        }
        return {"embed": {"table": _table(jax, k_table, c, padded_vocab, dt)},
                "groups": [block], "tail": [],
                "norm_f": {"scale": _leaf(jax, k_norm, (c["d"],), "n", dt)}}

    return jax.jit(build)(*_keys(jax, seed))


_JITS: dict = {}


def _jit(jax, key, make):
    """One jitted function per key for the life of the process, so a
    reference run compiles (or loads) each program once."""
    if key not in _JITS:
        _JITS[key] = jax.jit(make())
    return _JITS[key]


def layer_weights(jax, conf: dict, seed: int, layer: int, *,
                  served_dtype: str):
    """One layer's weights in float32, equal to the served values."""
    import jax.numpy as jnp
    c = dims(conf)
    dt = jnp.dtype(served_dtype)

    def make():
        def build(k_layers, layer):
            leaves = _layer_leaves(jax, jax.random.fold_in(k_layers, layer),
                                   c, dt)
            return {k: v.astype(jnp.float32) for k, v in leaves.items()}
        return build

    fn = _jit(jax, ("layer_weights", tuple(sorted(c.items())), served_dtype),
              make)
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

    fn = _jit(jax, ("head_weights", tuple(sorted(c.items())), served_dtype),
              make)
    _, k_table, k_norm = _keys(jax, seed)
    return fn(k_table, k_norm)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _quant_fp8(w):
    """Round a (fan_in, fan_out) weight to float8 e4m3 with one scale per
    output channel, back in bfloat16."""
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (w / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf / jnp.sqrt(var + eps) * scale).astype(x.dtype)


def _rope(x, pos, theta):
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _layer(h, W, c, q_block: int, quant: str | None):
    """One decoder layer over one sequence ``h`` (S, d)."""
    import jax
    import jax.numpy as jnp
    S = h.shape[0]
    H, KV, hd = c["H"], c["KV"], c["hd"]
    cd = jnp.bfloat16 if quant else jnp.float32
    Wm = {k: (_quant_fp8(v) if quant and v.ndim == 2 else v.astype(cd))
          for k, v in W.items()}

    def proj(x, name):
        y = jnp.dot(x.astype(cd), Wm[f"{name}.w"],
                    preferred_element_type=jnp.float32)
        if f"{name}.b" in Wm:
            y = y + Wm[f"{name}.b"].astype(jnp.float32)
        return y.astype(cd)

    pos = jnp.arange(S)
    x = _rms(h, W["ln1"], c["eps"])
    q = _rope(proj(x, "q").reshape(S, H, hd), pos, c["theta"])
    k = _rope(proj(x, "k").reshape(S, KV, hd), pos, c["theta"])
    v = proj(x, "v").reshape(S, KV, hd)
    G = H // KV
    outs = []
    for lo in range(0, S, q_block):          # causal: keys up to block end
        hi = min(lo + q_block, S)
        qb = q[lo:hi].reshape(hi - lo, KV, G, hd).astype(jnp.float32)
        kb, vb = k[:hi].astype(jnp.float32), v[:hi].astype(jnp.float32)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, kb) / math.sqrt(hd)
        mask = pos[lo:hi][:, None] >= pos[:hi][None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p, vb)
        outs.append(o.reshape(hi - lo, H * hd).astype(cd))
    attn = jnp.concatenate(outs, axis=0)
    h = h + proj(attn, "o").astype(h.dtype)
    x = _rms(h, W["ln2"], c["eps"])
    g = jnp.dot(x.astype(cd), Wm["gate"], preferred_element_type=jnp.float32)
    u = jnp.dot(x.astype(cd), Wm["up"], preferred_element_type=jnp.float32)
    m = (jax.nn.silu(g) * u).astype(cd)
    d = jnp.dot(m, Wm["down"], preferred_element_type=jnp.float32)
    return h + d.astype(h.dtype)


def _head(h, rows, norm_f, table, c, quant):
    """Logits at ``rows`` of ``h`` over the published vocabulary."""
    import jax.numpy as jnp
    x = _rms(h[rows], norm_f, c["eps"])
    head = _quant_fp8(table.T) if quant else table.T
    return jnp.dot(x.astype(head.dtype), head,
                   preferred_element_type=jnp.float32)


def forward_logits(jax, conf: dict, seed: int, seqs, rows, *,
                   served_dtype: str, quant: str | None = None,
                   bucket: int = 1024, q_block: int = 512,
                   row_block: int = 128):
    """Logits over the published vocabulary of each sequence in ``seqs``
    (int token arrays) at the positions ``rows[i]`` (each position's
    logits predict the next token).  Sequences are padded at the end to a
    multiple of ``bucket`` (causal attention: padding changes nothing
    before it), rows to a multiple of ``row_block``, so a few program
    shapes serve every sample.  Returns a list of float32 numpy arrays
    (len(rows[i]), V)."""
    import functools

    import jax.numpy as jnp
    c = dims(conf)
    ckey = tuple(sorted(c.items()))
    with jax.default_matmul_precision("highest"):
        table, norm_f = head_weights(jax, conf, seed,
                                     served_dtype=served_dtype)
        dt = jnp.bfloat16 if quant else jnp.float32
        embed = _jit(jax, ("embed", dt), lambda: lambda t, x: jnp.take(
            t, x, axis=0).astype(dt))
        hs = []
        for s in seqs:
            S = -(-len(s) // bucket) * bucket
            toks = np.zeros(S, np.int32)
            toks[:len(s)] = s
            hs.append(embed(table, toks))
        step = _jit(jax, ("layer", ckey, q_block, quant),
                    lambda: functools.partial(_layer, c=c, q_block=q_block,
                                              quant=quant))
        for layer in range(c["L"]):
            W = layer_weights(jax, conf, seed, layer,
                              served_dtype=served_dtype)
            hs = [step(h, W) for h in hs]
            del W
        head = _jit(jax, ("head", ckey, quant), lambda: functools.partial(
            _head, c=c, quant=quant))
        out = []
        for h, r in zip(hs, rows):
            r = np.asarray(r, np.int32)
            n = -(-len(r) // row_block) * row_block
            rp = np.zeros(n, np.int32)
            rp[:len(r)] = r
            out.append(np.asarray(head(h, rp, norm_f, table),
                                  np.float32)[:len(r)])
    return out


def served_gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at that position (0 where it is the best); a token
    outside the published vocabulary reads infinite."""
    tokens = np.asarray(tokens)
    best = ref_logits.max(axis=-1)
    ok = (tokens >= 0) & (tokens < ref_logits.shape[-1])
    safe = np.where(ok, tokens, 0)
    got = ref_logits[np.arange(len(tokens)), safe]
    return np.where(ok, best - got, np.inf)
