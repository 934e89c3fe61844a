"""The sparse layers' decode kernel: its share of its roofline over the
traced slice.

Time: the summed device time of the ops named ``kernel`` + anything (the
Pallas kernel ``sparse_attention_decode`` of ``ops/sparse_attention.py``: it
streams the pages the selection chose and nothing else; the scoring of the
compressed keys and the top-k before it are XLA fusions, in neither the
time nor the need). The least the chip could take is the larger of bytes /
peak bytes/s and FLOPs / peak FLOP/s for what the algorithm needs, from the
program's own counts — the ``event`` instants stamped inside the slice
carry ``pages_read``, which the decode program itself counted on the device
and returned behind its tokens: the chosen pages with a position to read,
summed over the step's rows, KV heads and sparse layers:

* a page read is its K and its V for one KV head: ``2 * page_size *
  head_dim`` elements of the cache's dtype;
* a token read costs ``4 * head_dim`` FLOPs for each query head of its KV
  head.

Pages resident and not chosen, padding rows and the compressed keys are not
the kernel's work.
"""

from .. import trace_reduce
from ..harness import log

_BYTES = {"bfloat16": 2, "bf16": 2, "native": 2, "int8": 1, "float32": 4}


def read(record, kernel, event):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not trace["planes"] or not peaks:
        return None
    seconds = trace_reduce.kernel_seconds(trace, [kernel])[0]
    if seconds <= 0:
        return None
    a, b = trace["t0"], trace["t0"] + trace["window_s"]
    pages = sum(e["attrs"]["pages_read"] for e in record.get("spans", ())
                if e["name"] == event and a <= e["ts"] < b)
    if not pages:
        return None
    m = record["model"]
    tokens = pages * m["serve"]["page_size"]
    nbytes = 2 * m["head_dim"] * tokens * _BYTES[m["serve"]["kv_dtype"]]
    flops = 4 * m["num_attention_heads"] // m["num_key_value_heads"] \
        * m["head_dim"] * tokens
    t_bytes = nbytes / peaks["hbm_bw_bytes"]
    t_flops = flops / peaks["peak_flops"]
    log(f"{kernel}: {seconds * 1e3:.1f} ms on the device for {pages} pages "
        f"read (rows x KV heads x sparse layers); bytes bound "
        f"{t_bytes * 1e3:.2f} ms, FLOPs bound {t_flops * 1e3:.3f} ms -> "
        f"{'bytes' if t_bytes >= t_flops else 'FLOPs'}-bound")
    return max(t_bytes, t_flops) / seconds * 100.0
