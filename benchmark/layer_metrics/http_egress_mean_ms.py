"""Gateway + HTTP replica: milliseconds a token lies between the
scheduler's hand-off and the socket (``/stats.request_path``: ``egress_s``
over ``handoffs``, the window's edges).  A hand-off is what one drain
recorded for one stream (``_deliver``: ``--decode-block`` tokens at once);
its stamp is the scheduler's, its end the return of the handler's write
of the hand-off's last line: the handler's wake-up, its turn at the GIL
among the other handlers, a ``json.dumps`` and two writes a token.
``None`` where the program has no such record."""
from request_path import per


def read(ctx):
    return per(ctx, ("egress_s",), "handoffs", 1e3)
