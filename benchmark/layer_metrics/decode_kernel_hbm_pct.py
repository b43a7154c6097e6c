"""Kernels: the share of the chip's peak HBM bandwidth that the KV the
decode kernel had to read explains.  Bytes: over the matched executions,
the tokens of KV held by the rows that decoded (``kv_tokens``, counted by
the program where the batch is built) x the decode steps the device ran x
the bytes a token holds on one chip (``bytes.kv_bytes_per_token`` from
the configuration file: the yardstick's number, not the program's).
Time: the own time of ``_paged_call.*`` in the trace (a chip's mean),
scaled by matched / all executions.  Since PR 25 the kernel walks a
row's live pages only, so this is close to a roofline share of the call,
and not one: the bytes count tokens where the kernel reads whole pages
(and block tables), and the value can pass 100 where the plane the kernel
reads is not in HBM (161.9 in the saturated long-context cell, whose MHA
pages are 1 MiB; ledger, PR 25; PERF.md section 6)."""
import importlib

from dispatch_join import join
from peaks import peaks_for

_bytes = importlib.import_module("bytes")      # benchmark/bytes.py


def kv_element_bytes(flags: list) -> int:
    """Bytes of one stored K or V element: 2 (bf16 pages) unless the
    serve flags quantize the pool to int8."""
    if "--kv-dtype" in flags:
        return 1 if flags[flags.index("--kv-dtype") + 1] == "int8" else 2
    return 2


def read(ctx):
    j, tr = join(ctx), ctx["trace"]
    if not j["pairs"] or not tr.get("op_self_s"):
        return None
    kernel_s = sum(t for name, t in tr["op_self_s"]
                   if name.startswith("_paged_call"))
    if not kernel_s:
        return None
    per_token = _bytes.kv_bytes_per_token(
        ctx["config"]["model_config"],
        kv_element_bytes(ctx["config"]["serve_flags"]),
        ctx["cell"]["chips"])
    read_bytes = per_token * sum(r["kv_tokens"] * r["steps"]
                                 for _, _, r in j["pairs"])
    bw = peaks_for(ctx["health"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * read_bytes / (kernel_s * j["share"] * bw)
