"""Gateway + HTTP replica: milliseconds a request spends between the
gateway's handler and the engine's queue, by the program's own stamps
(``/stats.request_path``, the window's edges): the seconds the gateway
held it before forwarding it (body read, routing; its header says so, a
duration), the replica handler's read and parse (entry of ``do_POST`` to
the body decoded and the ids built), and the engine's ``submit`` (to its
own ``t_submit``), over the requests the engine took.  What a request's
TTFT holds beside this and the engine's own time is the sockets' and the
egress of its first token.  ``None`` where the program has no such
record (the parent of the PR that brought it).

Earlier line ``[path]``: the three parts, a hand-off's egress (mean and
the longest since the start), the lines a hand-off, the writes a token,
the bytes a token and the handler threads' CPU a dispatch."""
from layer_metrics import delta
from request_path import per


def read(ctx):
    total = per(ctx, ("gateway_s", "read_parse_s", "submit_s"),
                "ingress_count", 1e3)
    if total is None:
        return None
    gw, parse, submit = (per(ctx, (k,), "ingress_count", 1e3)
                         for k in ("gateway_s", "read_parse_s", "submit_s"))
    egress = per(ctx, ("egress_s",), "handoffs", 1e3)
    lines = per(ctx, ("lines",), "handoffs")
    writes, nbytes = (per(ctx, (k,), "tokens") for k in ("writes", "bytes"))
    cpu = per(ctx, ("handler_cpu_s",), "seq", 1e3, section="dispatch_trace")

    def fmt(x):
        return "none" if x is None else f"{x:.3f}"

    print(f"[path] {delta(ctx, 'request_path', 'ingress_count')} requests "
          f"in the window, ms each: gateway {fmt(gw)}, read + parse "
          f"{fmt(parse)}, submit {fmt(submit)}; "
          f"{delta(ctx, 'request_path', 'handoffs')} hand-offs, egress mean "
          f"{fmt(egress)} ms (longest since the start "
          f"{1e3 * ctx['stats_close']['request_path']['egress_max_s']:.3f}), "
          f"{fmt(lines)} lines a hand-off, {fmt(writes)} writes and "
          f"{fmt(nbytes)} bytes a token; handler CPU {fmt(cpu)} ms a "
          f"dispatch", flush=True)
    return total
