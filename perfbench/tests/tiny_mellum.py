"""The tiny Mellum 2 configuration and traffic for CPU rehearsals of
``serve_open_loop_mellum`` (``tiny.py``'s companion): one period of the
published pattern at hidden 64, 8 query heads on 2 KV heads of 16, window 8
on pages of 4, YaRN whose original context (64 positions) is shorter than a
prompt, 8 experts chosen 2 at a time, window pages kept every 16 tokens, and
follow-up questions longer than the window. Nothing in BENCHMARK.json names
it."""

MODEL = {
    "hidden_size": 64, "moe_intermediate_size": 32, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
    "num_hidden_layers": 4, "rms_norm_eps": 1e-6, "sliding_window": 8,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "max_position_embeddings": 1024, "tie_word_embeddings": False,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 1000, "factor": 4,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1000}}}

SERVE = dict(MODEL, runner="serve_open_loop_mellum", serve={
    "dtype": "float32", "kv_dtype": "native", "max_len": 128,
    "page_size": 4, "slots_tried": [6], "hbm_free_share": 0.1,
    "workspace_gb": 0.0, "buckets": [6], "max_queue": 64,
    "window_boundary_tokens": 16, "window_boundary_pages": 48,
    "o_proj_init_scale": 0.25,
    "check": {"doc": 32, "question": 12, "new_tokens": 16, "beside": 3,
              "beside_prompt": 12, "beside_tokens": 96, "cache_pages": 2,
              "sample_chat": 3, "sample_doc": 1, "sample_tokens": 4}})

SESSIONS = {"schedule_seed": 9, "rate_rps": 4.0, "lead_in_s": 1,
            "lead_out_s": 3, "drain_limit_s": 30, "prompt_lens": [4, 12],
            "prompt_weights": [0.6, 0.4], "answer_lens": [4, 8],
            "session": {"doc_lens": [0, 32, 48],
                        "doc_weights": [0.55, 0.30, 0.15],
                        "questions": [2, 3], "gap_s": [0.3, 0.8],
                        "backfill_s": 2}}
