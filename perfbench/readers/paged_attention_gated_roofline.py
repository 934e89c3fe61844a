"""The paged decode kernel's share of its roofline where only every
``full_attention_interval``-th layer is attention and a head is the file's
``head_dim`` wide: its summed device time over the traced slice against the
larger of bytes / peak bytes/s and FLOPs / peak FLOP/s.

As ``paged_attention_kinds_roofline`` (which rows decoded with which context
inside the slice from the client's stamps), with what that configuration's
reader cannot know: this file names no ``layer_types`` — layer ``i`` of the
published model is full attention iff ``(i + 1) % full_attention_interval ==
0`` (the file's ``assumed.layer_pattern``) and the layers run are
``serve.layers_run``; every other layer keeps a state and reads no page.

* a decode row with ``t`` tokens of context reads, per attention layer, K
  and V of ``t`` positions: ``2 * num_key_value_heads * head_dim * t``
  elements of the pages' dtype;
* and does ``q . K`` and ``p . V`` for every query head: ``4 *
  num_attention_heads * head_dim * t`` FLOPs.

Padding rows of the bucket, the positions a row's last page does not hold
yet, the output gate and the projections around the kernel are not needed
work of it.
"""

from .. import trace_reduce
from ..harness import log

_BYTES = {"bfloat16": 2, "bf16": 2, "native": 2, "int8": 1, "float32": 4}


def attention_layers(model) -> int:
    """How many of the layers this file runs are full attention."""
    every = model["full_attention_interval"]
    return sum((i + 1) % every == 0 for i in model["serve"]["layers_run"])


def need(model, read_tokens: int) -> tuple:
    """(bytes, FLOPs) for ``read_tokens`` context positions summed over
    decode rows, in every attention layer."""
    layers, d = attention_layers(model), model["head_dim"]
    return (2 * model["num_key_value_heads"] * d * read_tokens * layers
            * _BYTES[model["serve"]["kv_dtype"]],
            4 * model["num_attention_heads"] * d * read_tokens * layers)


def read(record, kernel):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not trace["planes"] or not peaks:
        return None
    m = record["model"]
    if "full_attention_interval" not in m or "layers_run" not in m["serve"]:
        return None
    seconds = trace_reduce.kernel_seconds(trace, [kernel])[0]
    if seconds <= 0:
        return None
    a, b = trace["t0"], trace["t0"] + trace["window_s"]
    contexts = [r["prompt_len"] + i for r in record["requests"]
                for i, t in enumerate(r["tokens"]) if i and a <= t < b]
    if not contexts:
        return None
    nbytes, flops = need(m, sum(contexts))
    t_bytes = nbytes / peaks["hbm_bw_bytes"]
    t_flops = flops / peaks["peak_flops"]
    log(f"{kernel}: {seconds * 1e3:.1f} ms on the device for "
        f"{len(contexts)} rows reading {sum(contexts)} tokens in "
        f"{attention_layers(m)} attention layers; bytes bound "
        f"{t_bytes * 1e3:.2f} ms, FLOPs bound {t_flops * 1e3:.3f} ms -> "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'}-bound")
    return max(t_bytes, t_flops) / seconds * 100.0
