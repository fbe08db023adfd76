"""Mean live decode rows per forward pass dispatched in the window: over
the program's forward-pass dispatch spans, the sum of their ``rows``
times their passes over the sum of passes (a megastep makes ``n_steps``
passes; a chunk dispatched alone computes no decode row).  None when the
spans carry no ``rows``, as a program that does not record them."""
from bench import readings


def read(run):
    rows = passes = 0
    for s in run.engine_spans:
        if s.name == "dispatch:megastep":
            n = int(s.args["n_steps"])
        elif s.name in readings.ONE_PASS:
            n = 1
        else:
            continue
        r = (s.args or {}).get("rows")
        if r is None:
            return None
        rows += int(r) * n
        passes += n
    return rows / passes if passes else None
