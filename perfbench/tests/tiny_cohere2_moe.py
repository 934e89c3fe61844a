"""The tiny Command-A+ configuration and traffic for CPU rehearsals of
``serve_open_loop_moe`` (``tiny.py``'s companion: a file the benchmark has
is not edited): head_dim 16 on hidden 64, 8 heads on 2 KV heads, 8 experts
top-2 with 2 shared and experts 2-5 held, window 8 on pages of 4, one
period of the published pattern. Nothing in BENCHMARK.json names it."""

MODEL = {
    "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
    "num_hidden_layers": 4, "layer_norm_eps": 1e-5, "rope_theta": 50000,
    "sliding_window": 8, "layer_switch": 4, "num_experts": 4,
    "num_experts_per_tok": 2, "num_shared_experts": 2, "logit_scale": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2}

SERVE = dict(MODEL, runner="serve_open_loop_moe", serve={
    "dtype": "float32", "kv_dtype": "native", "max_len": 96, "page_size": 4,
    "slots_tried": [6], "hbm_free_share": 0.1, "workspace_gb": 0.0,
    "buckets": [1], "max_queue": 64, "experts_published": 8,
    "experts_held": [2, 4], "vocab_published": 768, "vocab_held": [0, 96],
    "o_proj_init_scale": 0.5})

SESSIONS = {"schedule_seed": 9, "rate_rps": 4.0, "lead_in_s": 1,
            "lead_out_s": 3, "drain_limit_s": 30, "prompt_lens": [4],
            "answer_lens": [4, 8],
            "session": {"doc_lens": [24, 40], "questions": [2, 3],
                        "gap_s": [0.3, 0.8], "backfill_s": 2}}
