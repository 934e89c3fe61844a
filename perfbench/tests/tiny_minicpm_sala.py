"""The tiny MiniCPM-SALA configuration and traffic for CPU rehearsals of
``serve_open_loop_sala`` (``tiny.py``'s companion): both mixers, head_dim 8
on hidden 32, 4 heads on 2 KV heads, blocks of 4 tokens of which the best 3
are kept past 16 tokens of context, the stage of published layers 1-4 of a
6-layer model. Nothing in BENCHMARK.json names it."""

_MIXERS = ["lightning-attn", "minicpm4", "lightning-attn", "lightning-attn",
           "minicpm4", "lightning-attn"]

MODEL = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "lightning_nh": 4,
    "lightning_nkv": 4, "lightning_head_dim": 8, "vocab_size": 96,
    "num_hidden_layers": 4, "mixer_types": _MIXERS[1:5],
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "qk_norm": True, "lightning_use_rope": True, "use_output_norm": True,
    "sparse_config": {"kernel_size": 2, "kernel_stride": 1, "block_size": 4,
                      "topk": 3, "init_blocks": 1, "window_size": 4,
                      "dense_len": 16}}

SERVE = dict(MODEL, runner="serve_open_loop_sala", serve={
    "dtype": "float32", "kv_dtype": "native", "max_len": 128, "page_size": 4,
    "slots_tried": [6], "hbm_free_share": 0.1, "workspace_gb": 0.0,
    "buckets": [1], "max_queue": 64, "state_snapshot_tokens": 8,
    "state_snapshot_gb": 0.001, "layers_published": 6,
    "layers_run": [1, 2, 3, 4], "mixer_types_published": _MIXERS,
    "check": {"doc": 40, "question": 4, "new_tokens": 8, "beside": 3,
              "beside_doc": 24, "beside_tokens": 96}})

SESSIONS = {"schedule_seed": 9, "rate_rps": 4.0, "lead_in_s": 1,
            "lead_out_s": 3, "drain_limit_s": 30, "prompt_lens": [4],
            "answer_lens": [4, 8],
            "session": {"doc_lens": [24, 40], "questions": [2, 3],
                        "gap_s": [0.3, 0.8], "backfill_s": 2}}
