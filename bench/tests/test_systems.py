"""The serving harness takes any system by name: each configuration's
reference is found by its ``system`` key, and the model's FLOPs and the
paged kernel's bytes are counted from the program's layer plan."""
import dataclasses
import glob
import json
import os
import sys
import types

import numpy as np
import pytest

from bench import reference
from bench.drivers import serve_common
from bench.tests import small
from bench.tests.cpu_cell import CHECKOUT, run_small


def bench_configs():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = {}
    for c in bench["configs"]:
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            out[c["name"]] = json.load(f)
    return out


# ---------------------------------------------------------------------------
# the lookup
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("system", ["qwen2", "jacobi"])
def test_reference_found_by_system(system):
    import importlib
    assert reference.find(system) is importlib.import_module(
        f"bench.reference.{system}")
    assert system in {c["system"] for c in bench_configs().values()}


@pytest.mark.parametrize("system", ["granite", "no_such_system"])
def test_unknown_system_names_the_missing_file(system):
    with pytest.raises(FileNotFoundError,
                       match=f"bench/reference/{system}.py"):
        reference.find(system)


def test_drivers_import_no_reference_by_name():
    for path in glob.glob(os.path.join(CHECKOUT, "bench", "drivers",
                                       "*.py")):
        with open(path) as f:
            src = f.read()
        assert "reference.qwen2" not in src, path
        assert "reference import qwen2" not in src, path
        assert "qkv_bias" not in src, path


def qwen2_conf(**over):
    with open(os.path.join(CHECKOUT, "bench", "configs",
                           "qwen2-1.5b.json")) as f:
        conf = json.load(f)
    conf.update(over)
    return conf


@pytest.mark.parametrize("key,value,field", [
    ("intermediate_size", 8192, "d_ff"),
    ("rope_theta", 10000.0, "rope_theta"),
    ("num_key_value_heads", 4, "n_kv_heads"),
])
def test_model_config_checks_decoder_and_system_sizes(key, value, field):
    serve_common.model_config(qwen2_conf())
    with pytest.raises(ValueError, match=f"{field}="):
        serve_common.model_config(qwen2_conf(**{key: value}))


# A stub reference for a bias-free system (mixtral-8x7b's smoke preset):
# its weights are the program's own initialisation, and its "reference"
# logits put all their mass on the token each sequence holds next (moved
# on by ``shift``): it shows which functions the harness calls, not that
# the program is right.
MIXTRAL_SMOKE = {"system": "stub_moe", "program_arch": "mixtral-8x7b",
                 "program_size": "smoke", "num_hidden_layers": 2,
                 "hidden_size": 64, "num_attention_heads": 4,
                 "num_key_value_heads": 2, "vocab_size": 512,
                 "max_position_embeddings": 256,
                 "tie_word_embeddings": False}


def stub_reference(calls: list):
    mod = types.ModuleType("bench.reference.stub_moe")
    mod.shift = 0

    def check_config(cfg, conf):
        calls.append("check_config")
        assert not cfg.qkv_bias

    def program_params(jax, conf, seed, *, padded_vocab, dtype):
        calls.append("program_params")
        from repro.configs import get_smoke_config
        from repro.models.transformer import init_params
        cfg = get_smoke_config(conf["program_arch"])
        assert cfg.padded_vocab == padded_vocab
        p = init_params(cfg, jax.random.PRNGKey(seed & 0xFFFFFFFF))
        return jax.tree.map(lambda x: x.astype(dtype), p)

    def forward_logits(jax, conf, seed, seqs, rows, *, served_dtype,
                       quant=None):
        calls.append("forward_logits")
        out = []
        for s, r in zip(seqs, rows):
            lg = np.zeros((len(r), conf["vocab_size"]), np.float32)
            nxt = (np.asarray(s)[np.asarray(r) + 1] + mod.shift) % lg.shape[1]
            lg[np.arange(len(r)), nxt] = 1.0
            out.append(lg)
        return out

    def served_gaps(ref_logits, tokens):
        calls.append("served_gaps")
        tokens = np.asarray(tokens)
        got = ref_logits[np.arange(len(tokens)), tokens]
        return ref_logits.max(axis=-1) - got

    for fn in (check_config, program_params, forward_logits, served_gaps):
        setattr(mod, fn.__name__, fn)
    return mod


@pytest.fixture
def stub_system(monkeypatch):
    """Registers the stub for system ``stub_moe`` and makes every Qwen2
    reference function raise; returns the list of stub calls."""
    from bench.reference import qwen2

    def qwen2_called(*a, **k):
        raise AssertionError("the Qwen2 reference was called")
    for name in ("check_config", "program_params", "forward_logits",
                 "served_gaps"):
        monkeypatch.setattr(qwen2, name, qwen2_called)
    calls: list = []
    monkeypatch.setitem(sys.modules, "bench.reference.stub_moe",
                        stub_reference(calls))
    return calls


def test_stub_system_passes_model_config(stub_system):
    # the Qwen2 file's own keys stay (intermediate_size 8960, q/k/v biases
    # stated nowhere): the stub's system does not check them
    conf = qwen2_conf(**MIXTRAL_SMOKE)
    cfg = serve_common.model_config(conf)
    assert cfg.name == "mixtral-8x7b-smoke" and not cfg.qkv_bias
    assert stub_system == ["check_config"]
    with pytest.raises(ValueError, match="n_layers="):
        serve_common.model_config({**conf, "num_hidden_layers": 3})


@pytest.mark.parametrize("shift", [0, 1])
def test_stub_system_runs_through_the_harness(tmp_path, stub_system,
                                              monkeypatch, shift):
    # shift 1: the stub's reference puts its mass on another token than
    # the one served, and the harness's check must read the stub's gap
    monkeypatch.setattr(sys.modules["bench.reference.stub_moe"], "shift",
                        shift)
    res = run_small(tmp_path, "qwen2-1.5b.chat", config=MIXTRAL_SMOKE,
                    traffic=small.SERVE_MIX, seed=5, seconds=2.0)
    assert res["correct"] is (shift == 0)
    assert res["checks"]["logit_gap"]["value"] == float(shift)
    for fn in ("check_config", "program_params", "forward_logits",
               "served_gaps"):
        assert fn in stub_system
    assert res["metrics"]["setup_s"]["value"] > 0


# ---------------------------------------------------------------------------
# FLOPs and kernel bytes from the layer plan
# ---------------------------------------------------------------------------


def parent_flops(cfg, n_params, chunks, decodes):
    """The counts as they were for a dense model of ``cfg.n_layers`` full
    attention layers with a tied head: kept to pin the plan's counts."""
    d, V = cfg.d_model, cfg.padded_vocab
    body = n_params - V * d
    attn = 4 * cfg.n_layers * cfg.n_heads * cfg.hd
    total = 0.0
    for _, start, valid in chunks:
        keys = valid * start + valid * (valid + 1) / 2
        total += 2 * body * valid + 2 * V * d + attn * keys
    for _, ctx in decodes:
        total += len(ctx) * (2 * body + 2 * V * d) + attn * sum(ctx)
    return total


def parent_paged_kernel_cost(cfg, page_size, decodes):
    L, KV, H, hd = cfg.n_layers, cfg.n_kv_heads, cfg.n_heads, cfg.hd
    G = H // KV
    page_bytes = page_size * hd * 2
    fl = by = 0.0
    for _, ctx in decodes:
        for c in ctx:
            old = c - 1
            pages = -(-old // page_size)
            by += L * KV * 2 * pages * page_bytes
            by += L * H * hd * 2 + L * KV * G * (hd + 2 * 128) * 4
            fl += L * 4 * H * hd * old
    return fl, by


CHUNKS = [(0.0, 0, 512), (0.1, 512, 512), (0.2, 1024, 37), (0.3, 0, 16),
          (0.4, 4096, 512), (0.5, 16, 1)]
DECODES = [(1.0, [1, 2, 17, 100, 511, 513, 4097]), (1.1, [5120] * 180),
           (1.2, list(range(2, 3000, 7))), (1.3, [])]


def served_cfg(arch, smoke=False):
    from repro.configs import get_config, get_smoke_config
    cfg = (get_smoke_config if smoke else get_config)(arch)
    return dataclasses.replace(cfg, param_dtype="bfloat16",
                               compute_dtype="bfloat16")


def program_shapes(cfg):
    import jax
    from repro.models.transformer import init_params
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


@pytest.mark.parametrize("smoke", [True, False], ids=["smoke", "full"])
def test_qwen2_counts_pinned_to_the_dense_formulas(smoke):
    import jax
    from bench.reference import qwen2
    conf = qwen2_conf(**(small.QWEN2 if smoke else {}))
    cfg = served_cfg("qwen2-1.5b", smoke)
    shapes = jax.eval_shape(lambda: qwen2.program_params(
        jax, conf, 2147483901, padded_vocab=cfg.padded_vocab,
        dtype="bfloat16"))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    body = serve_common.token_params(cfg, shapes)
    assert body == n_params - cfg.padded_vocab * cfg.d_model
    got = serve_common.flops(cfg, body, CHUNKS, DECODES)
    assert got == parent_flops(cfg, n_params, CHUNKS, DECODES)
    for page_size in (8, 16):
        assert serve_common.paged_kernel_cost(cfg, page_size, DECODES) == \
            parent_paged_kernel_cost(cfg, page_size, DECODES)


def test_zamba2_counts_the_shared_block_once_per_use():
    # 38 Mamba-2 layers and 6 uses of one shared attention + MLP block;
    # d 2048, 32 heads of 64 (kv 32), d_ff 8192, SSM d_inner 4096, state
    # 64, 64 heads, conv 4; vocab 32000, tied
    cfg = served_cfg("zamba2-1.2b")
    d, di, st, nh, ff, V = 2048, 4096, 64, 64, 8192, 32000
    ssm = (d + 3 * nh + (di + 2 * st) * (1 + 4)
           + d * (2 * di + 2 * st + nh) + di + di * d)
    shared = 4 * d * d + 2 * d + 3 * d * ff
    body = 38 * ssm + 6 * shared + d
    assert serve_common.token_params(cfg, program_shapes(cfg)) == body
    assert serve_common.attention_windows(cfg) == {None: 6}
    per_key = 4 * 32 * 64 * 6
    chunks = [(0.0, 0, 512), (0.1, 512, 300)]
    decodes = [(1.0, [100, 2000, 17])]
    want = (2 * body * 512 + 2 * V * d + per_key * (512 * 513 // 2)
            + 2 * body * 300 + 2 * V * d
            + per_key * (300 * 512 + 300 * 301 // 2)
            + 3 * (2 * body + 2 * V * d) + per_key * 2117)
    assert serve_common.flops(cfg, body, chunks, decodes) == want
    pages = 7 + 125 + 1                       # of 99, 1999 and 16 keys
    by = (6 * 32 * 2 * pages * 16 * 64 * 2
          + 3 * 6 * (32 * 64 * 2 + 32 * (64 + 256) * 4))
    fl = 4 * 32 * 64 * 6 * (99 + 1999 + 16)
    assert serve_common.paged_kernel_cost(cfg, 16, decodes) == (fl, by)


def test_gemma3_counts_windowed_layers_inside_their_window():
    # 29 local layers (window 1024) and 5 global; d 2560, 8 heads of 256
    # (kv 4), GELU MLP of 10240 (up, down), q/k norms; vocab 262144, tied
    cfg = served_cfg("gemma3-4b")
    d, ff, V, hd = 2560, 10240, 262144, 256
    layer = (d * 8 * hd + 2 * d * 4 * hd + 8 * hd * d + 2 * hd + 2 * d
             + 2 * d * ff)
    body = 34 * layer + d
    assert serve_common.token_params(cfg, program_shapes(cfg)) == body
    assert serve_common.attention_windows(cfg) == {1024: 29, None: 5}
    per_key = 4 * 8 * hd
    chunks = [(0.0, 0, 512), (0.1, 512, 1024)]
    # the second chunk's positions attend to 513 .. 1536 keys: a local
    # layer to at most 1024 of them
    local = (513 + 1024) * 512 // 2 + 1024 * 512
    full = (513 + 1536) * 1024 // 2
    decodes = [(1.0, [100, 3000])]
    want = (2 * body * 512 + 2 * V * d + per_key * 34 * (512 * 513 // 2)
            + 2 * body * 1024 + 2 * V * d
            + per_key * (29 * local + 5 * full)
            + 2 * (2 * body + 2 * V * d)
            + per_key * (29 * (100 + 1024) + 5 * (100 + 3000)))
    assert serve_common.flops(cfg, body, chunks, decodes) == want
    # context 100: 99 cached keys in 7 pages, window or not; context 3000:
    # a local layer's 1023 cached keys (positions 1976 .. 2998) lie in
    # pages 123 .. 187, a global layer's 2999 in 188 pages
    pages = 29 * (7 + 65) + 5 * (7 + 188)
    keys = 29 * (99 + 1023) + 5 * (99 + 2999)
    by = (4 * 2 * pages * 16 * hd * 2
          + 2 * 34 * (8 * hd * 2 + 4 * 2 * (hd + 256) * 4))
    assert serve_common.paged_kernel_cost(cfg, 16, decodes) == (
        per_key * keys, by)


@pytest.mark.parametrize("start,valid,window", [
    (0, 512, None), (0, 512, 1024), (512, 1024, 1024), (2048, 512, 1024),
    (1000, 40, 1024), (3, 5, 4), (0, 1, 1)])
def test_chunk_keys_counts_each_position(start, valid, window):
    want = sum(min(p + 1, window or p + 1)
               for p in range(start, start + valid))
    assert serve_common.chunk_keys(start, valid, window) == want


@pytest.mark.parametrize("window", [None, 1024, 40, 16, 17, 2])
def test_window_pages_counts_each_key(window):
    for ctx in range(1, 1200, 13):
        lo = 0 if window is None else max(0, ctx - window)
        cached = range(lo, ctx - 1)           # the new token is merged apart
        want = (len(cached), len({p // 16 for p in cached}))
        assert serve_common.window_pages(ctx, window, 16) == want


def test_mixtral_counts_top_k_experts_and_an_untied_head_once():
    # 32 layers of sliding-window attention (4096) and 8 experts, top 2;
    # d 4096, 32 heads of 128 (kv 8), expert d_ff 14336; vocab 32000,
    # output head not tied: outside the body, counted once per logit row
    cfg = served_cfg("mixtral-8x7b")
    d, ff, V = 4096, 14336, 32000
    layer = (2 * d * d + 2 * d * 8 * 128 + 2 * d + d * 8
             + 2 * 3 * d * ff)
    body = 32 * layer + d
    assert serve_common.token_params(cfg, program_shapes(cfg)) == body
    assert serve_common.attention_windows(cfg) == {4096: 32}
    decodes = [(1.0, [100, 5000])]
    want = 2 * (2 * body + 2 * V * d) + 4 * 32 * 128 * 32 * (100 + 4096)
    assert serve_common.flops(cfg, body, [], decodes) == want
