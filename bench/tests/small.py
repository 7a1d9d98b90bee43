"""Small configurations for running cells on the CPU."""

# at n 256 and 40 iterations the program reads x_err 0.8e-7 to 1.7e-7
# against the float32 reference, its bfloat16 control 3.0e-5 to 4.5e-5
# (CPU, seeds 1-12)
JACOBI = {"n": 256, "limits": {"x_err": 3e-6}}
JACOBI_MIX = {"iterations": 40}

QWEN2 = {"num_hidden_layers": 3, "hidden_size": 96, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 192,
         "vocab_size": 512, "max_position_embeddings": 256,
         "program_size": "smoke",
         "serving": {"engine": "hypar", "strategy": "greedy",
                     "paged_attention": "auto", "page_size": 16,
                     "prefill_chunk": 32, "prefix_cache": True,
                     "reserve": "lifetime", "max_len": 160,
                     "num_pages": 200},
         # at this size, on SERVE_MIX's 3 s window, the bf16 program reads
         # 0 to 0.0088 against the float32 reference, its fp8 control
         # 0.041 to 0.112 (CPU, seeds 1-13), a fault over 1
         "limits": {"logit_gap": 0.02}}
SERVE_MIX = {"prompt_tokens": {"dist": "lognormal", "median": 40,
                               "sigma": 1.0, "min": 8, "max": 100},
             "output_tokens": {"dist": "lognormal", "median": 16,
                               "sigma": 0.9, "min": 4, "max": 40},
             "slots": 4, "rate_per_s": 6.0, "lead_in_s": 0.5,
             "backlog": 1500, "check": {"tokens": 200, "max_requests": 8}}

BACKLOG_MIX = {"prompt_tokens": {"dist": "lognormal", "median": 40,
                                 "sigma": 0.8, "min": 8, "max": 60},
               "output_tokens": {"dist": "lognormal", "median": 40,
                                 "sigma": 0.6, "min": 10, "max": 90},
               "backlog": 2000, "block": 8, "slots": 4, "in_flight": 8,
               "check": {"tokens": 200, "max_requests": 8}}

# cells whose files are in bench/ but which BENCHMARK.json does not list yet
# (PERF.md, Open questions): the tests still drive them
CELLS = {
    "jacobi-7209.x4": (
        {"name": "jacobi-dense-7209-x4",
         "file": "bench/configs/jacobi-dense-7209-x4.json"},
        {"name": "jacobi-7209.x4", "config": "jacobi-dense-7209-x4",
         "traffic": "paper", "chips": 4}),
}
