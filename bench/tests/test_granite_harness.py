"""granite-4.0-h-micro through the serving harness: its configuration and
reference found by ``system``, FLOPs and kernel bytes counted from the
program's layer plan (36 ``ssm`` and 4 ``dense`` layers), the two readers
it brings (``decode_roofline``, ``ssd_scan_roofline``) on synthetic
traces, and the SMOKE preset run whole on the CPU."""
import copy
import dataclasses
import json
import os
import types

import numpy as np
import pytest

from bench import reference
from bench.drivers import serve_common
from bench.lib.cell import metric_reader
from bench.tests import small
from bench.tests.cpu_cell import CHECKOUT, run_small

CELL = "granite-4.0-h-micro.batch32"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the SMOKE preset's sizes; at this size, on BACKLOG_MIX's 1 s window, the
# bf16 program reads 0.00025 to 0.00048 against the float32 reference, its
# fp8 control 0.0035 to 0.0059 (CPU, seeds 1-12 and 1-6)
SMOKE = {"num_hidden_layers": 20, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "shared_intermediate_size": 128, "intermediate_size": 128,
         "vocab_size": 512, "max_position_embeddings": 256,
         "layer_types": PERIOD * 2, "mamba_n_heads": 8, "mamba_d_head": 16,
         "mamba_d_state": 16, "mamba_chunk_size": 16,
         "attention_multiplier": 1 / 16, "program_size": "smoke",
         "serving": {"engine": "hypar", "strategy": "greedy",
                     "paged_attention": "auto", "page_size": 16,
                     "prefill_chunk": 32, "prefix_cache": False,
                     "reserve": "lifetime", "max_len": 160,
                     "num_pages": 60},
         "limits": {"logit_gap": 0.0015}}
D, DI, N, NH, FF, V, W = 2048, 4096, 128, 64, 8192, 100352, 4
MAMBA = (2 * D + D * (2 * DI + 2 * N + NH) + (W + 1) * (DI + 2 * N)
         + 3 * NH + DI + DI * D + 3 * D * FF)
ATTN = 2 * D + 2 * D * 2048 + 2 * D * 512 + 3 * D * FF
BODY = 36 * MAMBA + 4 * ATTN + D
STATE = 36 * (64 * 64 * 128 + 3 * (DI + 2 * N)) * 4


def granite_conf(**over) -> dict:
    with open(os.path.join(CHECKOUT, "bench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        conf = json.load(f)
    conf.update(copy.deepcopy(over))
    return conf


def served_cfg():
    return serve_common.model_config(granite_conf())


def test_model_config_and_reference_accept_the_file():
    cfg = served_cfg()
    assert cfg.name == "granite-4.0-h-micro"
    assert cfg.param_dtype == "bfloat16" and not cfg.use_rope
    assert reference.find("granite").__name__ == "bench.reference.granite"


def _swap(types_, i, j):
    out = list(types_)
    out[i], out[j] = out[j], out[i]
    return out


@pytest.mark.parametrize("key,value", [
    ("embedding_multiplier", 1), ("attention_multiplier", 0.125),
    ("residual_multiplier", 1.0), ("logits_scaling", 1),
    ("rms_norm_eps", 1e-6), ("mamba_d_state", 64), ("mamba_n_heads", 32),
    ("position_embedding_type", "rope"), ("attention_bias", True),
    ("mamba_proj_bias", True), ("shared_intermediate_size", 4096)])
def test_check_config_rejects_a_changed_value(key, value):
    with pytest.raises(ValueError, match=key):
        serve_common.model_config(granite_conf(**{key: value}))


def test_check_config_rejects_other_layer_types():
    types_ = granite_conf()["layer_types"]
    with pytest.raises(ValueError, match="layer_types"):
        serve_common.model_config(granite_conf(
            layer_types=_swap(types_, 4, 5)))


def test_plan_kinds_and_token_params_at_full_width():
    import jax
    cfg = served_cfg()
    kinds = serve_common.plan_kinds(cfg)
    assert (kinds.count("ssm"), kinds.count("dense"), len(kinds)) == (36, 4,
                                                                       40)
    assert [i for i, k in enumerate(kinds) if k == "dense"] == [5, 15, 25,
                                                                 35]
    ref = reference.find("granite")
    shapes = jax.eval_shape(lambda: ref.program_params(
        jax, granite_conf(), 2147483901, padded_vocab=cfg.padded_vocab,
        dtype="bfloat16"))
    # every Mamba-2 layer's MLP (3 x 2048 x 8192) is in the body
    assert serve_common.token_params(cfg, shapes) == BODY
    assert BODY + V * D == cfg.n_params() == 3_191_396_096


def test_flops_and_paged_kernel_cost_by_hand():
    cfg = served_cfg()
    assert serve_common.attention_windows(cfg) == {None: 4}
    per_key = 4 * 32 * 64 * 4                   # 4 attention layers
    chunks = [(0.0, 0, 512), (0.1, 512, 300)]
    decodes = [(1.0, [100, 2000, 17])]
    want = (2 * BODY * 512 + 2 * V * D + per_key * (512 * 513 // 2)
            + 2 * BODY * 300 + 2 * V * D
            + per_key * (300 * 512 + 300 * 301 // 2)
            + 3 * (2 * BODY + 2 * V * D) + per_key * 2117)
    assert serve_common.flops(cfg, BODY, chunks, decodes) == want
    pages = 4 * (7 + 125 + 1)                   # of 99, 1999 and 16 keys
    by = (8 * 2 * pages * 16 * 64 * 2
          + 3 * 4 * (32 * 64 * 2 + 8 * 4 * (64 + 256) * 4))
    fl = 4 * 32 * 64 * 4 * (99 + 1999 + 16)
    assert serve_common.paged_kernel_cost(cfg, 16, decodes) == (fl, by)


# ---------------------------------------------------------------------------
# the two readers on synthetic traces
# ---------------------------------------------------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000


class _Recorder:
    def __init__(self, decodes):
        self.decodes = decodes

    def in_span(self, lo, hi):
        return [], [d for d in self.decodes if lo <= d[0] <= hi]


def _cell(ops=(), modules=(), data=None):
    summary = {"window": [0, 1000 * MS],
               "devices": {"/device:TPU:0": {"ops": list(ops),
                                             "modules": list(modules)}},
               "host": []}
    return types.SimpleNamespace(summary=summary, trace_span=(0.0, 10.0),
                                 data=data or {}, peaks=PEAKS)


def test_decode_roofline_by_hand():
    cfg = served_cfg()
    decodes = [(1.0, [1000] * 32), (2.0, [1000] * 31), (20.0, [5] * 32)]
    cell = _cell(modules=[["jit__decode(7)", 0, 20 * MS],
                          ["jit__decode(7)", 30 * MS, 22 * MS],
                          ["jit__chunk(3)", 60 * MS, 9 * MS]],
                 data={"recorder": _Recorder(decodes), "cfg": cfg,
                       "body": BODY, "page_size": 16})
    # the two decodes inside the span, 63 live slots: each slot's state
    # read and written, and at context 1000 its 999 cached keys in 63
    # pages of each of the 4 attention layers, with its query and outputs
    kv = (8 * 2 * 4 * 63 * 16 * 64 * 2
          + 4 * (32 * 64 * 2 + 8 * 4 * (64 + 256) * 4))
    per_step = 2 * (BODY + V * D) + 63 * (2 * STATE + kv) / 2
    want = per_step / 819e9 / 0.021 * 100
    got = metric_reader("decode_roofline.granite").read(cell)
    assert got == pytest.approx(want, rel=1e-9)


def test_decode_roofline_reads_nothing_without_the_counter(monkeypatch):
    from repro.serve.engine import PagedEngine
    monkeypatch.delattr(PagedEngine, "ssm_state_bytes")
    cell = _cell(modules=[["jit__decode(7)", 0, 20 * MS]],
                 data={"recorder": _Recorder([(1.0, [10] * 32)]),
                       "cfg": served_cfg(), "body": BODY, "page_size": 16})
    assert metric_reader("decode_roofline.granite").read(cell) is None


# the kernel's op as the profiler names it: its HLO text with the
# operands' shapes (a 512-token chunk: two chunks of 256)
SSD_OP = ('%_unknown_.1 = (f32[2,64,256,64]{3,2,1,0:T(8,128)S(1)}, '
          'f32[2,64,128,64]{3,2,1,0:T(8,128)S(1)}) custom-call('
          'f32[2,64,256,64]{3,2,1,0:T(8,128)S(1)} %copy.21, '
          'f32[2,64,256,1]{3,2,1,0:T(8,128)S(1)} %copy.22, '
          'f32[2,64,256,1]{3,2,1,0:T(8,128)S(1)} %copy.23, '
          'f32[2,256,128]{2,1,0:T(8,128)S(1)} %copy-done.1, '
          'f32[2,256,128]{2,1,0:T(8,128)S(1)} %copy-done.2), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints')
PAGED_OP = ('%closed_call.10 = (f32[32,8,8,128]) custom-call('
            's32[32,320]{1,0} %p, s32[32]{0} %l, bf16[32,8,8,128]{3,2,1,0} '
            '%q), custom_call_target="tpu_custom_call"')


def test_ssd_scan_roofline_by_hand():
    cell = _cell(
        modules=[["jit__chunk(3)", 100 * MS, 50 * MS],
                 ["jit__decode(7)", 200 * MS, 30 * MS]],
        ops=[[SSD_OP, 110 * MS, 2 * MS], [SSD_OP, 120 * MS, 3 * MS],
             [PAGED_OP, 130 * MS, 1 * MS],     # not the SSD kernel
             [SSD_OP, 205 * MS, 1 * MS]])      # not in a chunk program
    # per call: x, dt, the decay, B, C read; y and the state written, f32
    by = 4 * (2 * 64 * 256 * 64 + 2 * 2 * 64 * 256 + 2 * 2 * 256 * 128
              + 2 * 64 * 64 * (256 + 128))
    fl = (2 * 2 * 256 * 256 * 128 + 2 * 2 * 64 * 256 * 256 * 64
          + 2 * 2 * 64 * 256 * 128 * 64)
    assert (fl, by) == (1_644_167_168, 21_757_952)
    want = 2 * max(fl / 197e12, by / 819e9) / 0.005 * 100
    got = metric_reader("ssd_scan_roofline.granite").read(cell)
    assert got == pytest.approx(want, rel=1e-9)
    assert metric_reader("ssd_scan_roofline.granite").read(
        _cell(modules=[["jit__chunk(3)", 0, 5 * MS]],
              ops=[[PAGED_OP, 1 * MS, 1 * MS]])) is None


# ---------------------------------------------------------------------------
# whole runs on the CPU
# ---------------------------------------------------------------------------


def test_smoke_preset_runs_through_serve_backlog(tmp_path):
    res = run_small(tmp_path, CELL, config=SMOKE, traffic=small.BACKLOG_MIX,
                    seed=2147483905, seconds=2.0)
    assert res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= SMOKE["limits"][
        "logit_gap"]
    assert res["metrics"]["serve_out_tok_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["failed"] == 0


def test_fp8_control_through_the_harness_is_not_correct(tmp_path):
    res = run_small(tmp_path, CELL, config=SMOKE, traffic=small.BACKLOG_MIX,
                    seed=2147483906, seconds=2.0, control=True)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > SMOKE["limits"]["logit_gap"]
