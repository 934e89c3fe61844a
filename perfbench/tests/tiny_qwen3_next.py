"""The tiny Qwen3-Next configuration and traffic for CPU rehearsals of
``serve_open_loop_qwen3_next`` (``tiny.py``'s companion): two periods of
(delta, delta, full) at hidden 32, 2 of 8 experts' quarters held (4 of 8,
chosen 3 at a time), half the vocabulary. Nothing in BENCHMARK.json names
it."""

MODEL = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "partial_rotary_factor": 0.5, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "full_attention_interval": 3,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
    "linear_num_key_heads": 2, "linear_value_head_dim": 8,
    "linear_num_value_heads": 4, "num_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "intermediate_size": 80, "vocab_size": 64, "num_hidden_layers": 6,
    "max_position_embeddings": 512}

SERVE = dict(MODEL, runner="serve_open_loop_qwen3_next", serve={
    "dtype": "float32", "kv_dtype": "native", "state_dtype": "float32",
    "max_len": 192, "page_size": 4, "slots_tried": [6],
    "hbm_free_share": 0.1, "workspace_gb": 0.0, "buckets": [6],
    "max_queue": 64, "state_snapshot_tokens": 8, "state_snapshot_gb": 0.001,
    "layers_published": 9, "layers_run": [0, 1, 2, 3, 4, 5],
    "experts_published": 8, "experts_held": [0, 4],
    "vocab_published": 128, "vocab_held": [0, 64], "o_proj_init_scale": 1.0,
    "check": {"doc": 40, "question": 4, "new_tokens": 24, "beside": 3,
              "beside_doc": 24, "beside_question": 4, "beside_tokens": 96}})

SESSIONS = {"schedule_seed": 9, "rate_rps": 4.0, "lead_in_s": 1,
            "lead_out_s": 3, "drain_limit_s": 30, "prompt_lens": [4],
            "answer_lens": [8, 16],
            "session": {"doc_lens": [24, 40], "questions": [1, 2],
                        "question_weights": [0.4, 0.6],
                        "gap_s": [0.3, 0.8], "backfill_s": 2}}
