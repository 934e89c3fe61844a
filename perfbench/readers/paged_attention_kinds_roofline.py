"""The paged decode kernel's share of its roofline where layers keep pages
by kind: a sliding-window layer needs the keys inside its window only.

As ``paged_attention_roofline`` (the kernel's summed device time in the
slice against the larger of bytes / peak bytes/s and FLOPs / peak FLOP/s;
which rows ran with which context from the client's stamps), with the two
things that configuration's reader cannot know: ``head_dim`` is the file's
own key (not ``hidden_size / num_attention_heads``), and per decode row
with ``t`` tokens of context a layer of kind ``sliding_attention`` reads
``min(t, sliding_window)`` tokens where a ``full_attention`` layer reads
``t``. Pages below the window, padding rows and the page a context only
partly fills are not needed work.
"""

from .. import trace_reduce
from ..harness import log

_BYTES = {"bfloat16": 2, "bf16": 2, "native": 2, "int8": 1, "float32": 4}


def read(record, kernel):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not trace["planes"] or not peaks:
        return None
    seconds = trace_reduce.kernel_seconds(trace, [kernel])[0]
    if seconds <= 0:
        return None
    m = record["model"]
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    window = m["sliding_window"]
    a, b = trace["t0"], trace["t0"] + trace["window_s"]
    contexts = [r["prompt_len"] + i for r in record["requests"]
                for i, t in enumerate(r["tokens"]) if i and a <= t < b]
    read_tokens = sum(
        sum(min(t, window) if kind == "sliding_attention" else t
            for kind in kinds) for t in contexts)
    nbytes = (2 * m["num_key_value_heads"] * m["head_dim"] * read_tokens
              * _BYTES[m["serve"]["kv_dtype"]])
    flops = 4 * m["num_attention_heads"] * m["head_dim"] * read_tokens
    t_bytes = nbytes / peaks["hbm_bw_bytes"]
    t_flops = flops / peaks["peak_flops"]
    log(f"{kernel}: {seconds * 1e3:.1f} ms on the device for "
        f"{len(contexts)} rows reading {read_tokens} tokens over "
        f"{len(kinds)} layers; bytes bound {t_bytes * 1e3:.2f} ms, FLOPs "
        f"bound {t_flops * 1e3:.3f} ms -> "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'}-bound")
    return max(t_bytes, t_flops) / seconds * 100.0
