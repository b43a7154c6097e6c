"""Traffic generators, found by the name a mix file gives under
``generator``.  Each module has ``make(params, seed, vocab, scale)`` and
returns a plan: ``initial()`` gives the first requests and
``on_done(request, tokens, now_s)`` those a finished request sets off.
All randomness comes from ``random.Random`` seeded from ``seed``, so the
same seed gives the same plan, byte for byte."""
