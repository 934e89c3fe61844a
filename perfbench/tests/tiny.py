"""Tiny Mistral-shaped configurations and traffic for CPU rehearsals of the
runners (grouped-query 4:1, every mechanism of the real cells, no real
size). Not part of the benchmark: nothing in BENCHMARK.json names them."""

import time

MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 1, "vocab_size": 128, "num_hidden_layers": 2,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 1000000.0, "tie_word_embeddings": False}

SERVE = dict(MODEL, runner="serve_open_loop", serve={
    "dtype": "float32", "kv_dtype": "native", "max_len": 256,
    "page_size": 16, "slots": 4, "num_pages": 65, "buckets": [1, 4],
    "max_queue": 64})

TRAIN = dict(MODEL, runner="train_steps", train={
    "amp_level": "O2", "amp_dtype": "bfloat16", "scan_layers": True,
    "recompute": True, "optimizer": {
        "name": "AdamW", "learning_rate": 1e-4,
        "args": {"use_multi_tensor": False, "moment_dtype": "int8",
                 "use_master_weights": False}}})

TRAIN_MESH = dict(MODEL, runner="train_steps", train={
    "mesh": {"dp": 2, "mp": 2}, "amp_level": "O2", "amp_dtype": "bfloat16",
    "scan_layers": False, "recompute": False,
    "optimizer": {"name": "AdamW", "learning_rate": 1e-4, "args": {}}})

CHAT = {"schedule_seed": 7, "rate_rps": 6.0, "lead_in_s": 1, "lead_out_s": 3,
        "drain_limit_s": 30, "prompt_lens": [8, 24],
        "answer_lens": [4, 8],
        "session": {"doc_lens": [0], "questions": [1], "gap_s": [0, 0]}}

DOCQA = {"schedule_seed": 8, "rate_rps": 4.0, "lead_in_s": 1,
         "lead_out_s": 3, "drain_limit_s": 30, "prompt_lens": [16],
         "answer_lens": [4, 8],
         "session": {"doc_lens": [32, 64], "questions": [2, 3],
                     "gap_s": [0.3, 0.8], "backfill_s": 2}}

STEPS = {"seq": 64, "batch": 1}
STEPS_DP2 = {"seq": 64, "batch": 2}


def ctx(config, traffic, *, chips=1, seconds=2.0, trace=0, seed=2 ** 31 + 11,
        workload="tiny"):
    return {"workload": workload, "chips": chips, "config": config,
            "traffic": traffic, "seed": seed, "seconds": seconds,
            "trace": trace, "sweep": [], "on_chip": False,
            "t_start": time.monotonic()}
