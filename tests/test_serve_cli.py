"""The serving CLI (``repro.launch.serve``) and the job-file placement
preview (``repro.launch.dryrun``), run in-process on smoke configurations:
each mode runs end to end, each refused combination of options exits with
code 2, and the preview places every job of every segment."""
import pytest

from repro.launch import dryrun, serve

TRACE = ["--smoke", "--trace", "--n-requests", "6", "--max-new", "4",
         "--prompt-lens", "6", "12"]
PAGED = ["--smoke", "--trace", "--paged", "--page-size", "8",
         "--prefill-chunk", "16", "--max-new", "4"]


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    # keep JAX's global compile-cache settings and the checkout untouched
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)


def test_wave_mode(monkeypatch, capsys):
    engines = []

    class Spy(serve.Engine):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            engines.append(self)

    monkeypatch.setattr(serve, "Engine", Spy)
    assert serve.main(["--smoke", "--batch", "2", "--prompt-len", "8",
                       "--max-new", "4", "--requests", "2"]) is None
    out = capsys.readouterr().out
    assert "wave 0 (compile)" in out and "wave 1: generated (2, 4)" in out
    assert len(engines) == 1
    assert engines[0].trace_count("decode") == 1


# (argv, requests served, requests shed, what else the run must show)
RUNS = {
    "dense_direct": (TRACE, 6, 0, {}),
    "dense_hypar": (TRACE + ["--engine", "hypar"], 6, 0, {}),
    "paged_ref": (PAGED + ["--paged-attn-impl", "ref", "--n-requests", "6",
                           "--prompt-lens", "6", "20"], 6, 0,
                  {"paged_attn_impl": "ref"}),
    "paged_interpret": (PAGED + ["--paged-attn-impl", "interpret",
                                 "--n-requests", "6", "--prompt-lens", "6",
                                 "20"], 6, 0,
                        {"paged_attn_impl": "interpret"}),
    "demand_preempts": (PAGED + ["--reserve", "demand", "--num-pages", "8",
                                 "--batch", "4", "--n-requests", "8",
                                 "--max-new", "6", "--prompt-lens", "14",
                                 "15"], 8, 0, {"reserve": "demand"}),
    "prefix_hits": (PAGED + ["--prefix-cache", "--shared-prefix-len", "16",
                             "--n-requests", "6", "--prompt-lens", "24",
                             "40"], 6, 0, {"prefix_cache": True}),
    "ttft_deadline_sheds": (TRACE + ["--ttft-deadline", "1e-6"], 0, 6, {}),
    "granite_chunk_path": (PAGED + ["--arch", "granite-4.0-h-micro",
                                    "--n-requests", "5", "--prompt-lens",
                                    "6", "20"], 5, 0,
                           {"paged_attn_impl": "ref"}),
}


@pytest.mark.parametrize("name", list(RUNS))
def test_trace_run(name):
    argv, served, shed, want = RUNS[name]
    stats = serve.main(argv)
    assert stats["n_requests"] == served
    assert stats["n_rejected"] == shed
    assert stats["trace_counts"]["decode"] == 1
    for key, value in want.items():
        assert stats[key] == value, key
    if name == "demand_preempts":
        assert stats["preempt_count"] > 0
        assert stats["outcomes"] == {"completed": served}
    if name == "prefix_hits":
        assert stats["prefix_hit_rate"] > 0
    if name == "ttft_deadline_sheds":
        assert stats["shed_deadline"] == shed
    if name == "granite_chunk_path":
        assert stats["ssm_state_bytes"] > 0
        assert stats["trace_counts"]["chunk_prefill"] >= 1


REFUSED = {
    "store_needs_hypar": ["--store", "s.db"],
    "resume_needs_store_and_demand": ["--engine", "hypar", "--resume"],
    "paged_needs_trace": ["--paged"],
    "demand_needs_paged": ["--reserve", "demand"],
    "watermark_needs_demand": ["--admit-watermark", "2"],
    "prefix_cache_needs_paged": ["--prefix-cache"],
    "shared_prefix_needs_trace": ["--shared-prefix-len", "4"],
    "prefix_admit_positive": ["--prefix-admit", "0"],
    "mesh_malformed": ["--mesh", "2"],
    "mesh_needs_paged": ["--trace", "--mesh", "2,1"],
    "deadline_needs_trace": ["--ttft-deadline", "1.0"],
    "store_gc_needs_store": ["--store-gc", "60"],
}


@pytest.mark.parametrize("name", list(REFUSED))
def test_refused_arguments(name, capsys):
    with pytest.raises(SystemExit) as exc:
        serve.main(["--smoke"] + REFUSED[name])
    assert exc.value.code == 2
    assert "error:" in capsys.readouterr().err


@pytest.mark.parametrize("strategy", ["greedy", "cost"])
def test_jobfile_preview(tmp_path, capsys, strategy):
    path = tmp_path / "jobs.txt"
    path.write_text("J1(1,0,0), J2(2,1,0);\nJ3(3,0,R1 R2);\n")
    plans = dryrun.main(["--jobfile", str(path), "--workers", "2",
                         "--strategy", strategy])
    assert [[p.job.name for p in seg] for seg in plans] == [["J1", "J2"],
                                                            ["J3"]]
    assert all(0 <= p.worker.wid < 2 for seg in plans for p in seg)
    out = capsys.readouterr().out
    assert out.count("-> worker") == 3
