"""Kernels: the most contraction tiles any grouped matmul of the engine's
programs cuts ``k`` into, from the static ``/stats.moe.gmm`` (one line a
distinct call shape traced: ``{m, k, n, tiles, tiles_k, rhs_tile_bytes,
vmem_limit_bytes}``).  1 = every call reads an expert's ``[k, tn]`` slice
once a group however many row tiles the group covers; 2-4 = a slab
streams it again for every row tile (the program before PR 63 at the
bf16 configurations' widths).  ``None`` from a program without the
entry, from a model without experts, and where no traced shape is one the
kernel covers."""


def read(ctx):
    lines = ctx["stats_close"].get("moe", {}).get("gmm")
    if not lines:
        return None
    return max(line["tiles_k"] for line in lines)
