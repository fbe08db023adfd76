"""Mean host milliseconds of the engine's work steps in the window: each
``engine.step`` span less the top-level device spans inside it (dispatch
launch and the readback wait).  The arithmetic of the program's
``attribute_steps``, in linear time."""


def read(run):
    spans = sorted((s for s in run.engine_spans if s.dur is not None),
                   key=lambda s: (s.ts, -s.dur))
    steps = [s for s in spans if s.name == "engine.step"]
    top, reach = [], -1
    for s in spans:
        if s.cat == "device":
            if s.ts + s.dur > reach:        # not inside an earlier one
                top.append(s)
            reach = max(reach, s.ts + s.dur)
    host, j = [], 0
    for st in steps:
        end = st.ts + st.dur
        while j < len(top) and top[j].ts < st.ts:
            j += 1
        dev, k = 0, j
        while k < len(top) and top[k].ts + top[k].dur <= end:
            dev += top[k].dur
            k += 1
        if dev > 0:
            host.append((st.dur - dev) / 1e6)
    return sum(host) / len(host) if host else None
