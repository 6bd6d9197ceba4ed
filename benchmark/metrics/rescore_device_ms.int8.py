"""rescore_device_ms.int8 — device time of one call of the rescore program of a
UserRows query (``jit__rescore_gather``: the user rows and the k' shortlisted
item rows gathered out of the resident int8 tables, dequantized, their f32
dots, the top-k), in ms: the program's device seconds in the trace over its
runs. The dequantizing gather is its whole cost. None where the trace holds no
such program."""

PROGRAM = "jit__rescore_gather"


def read(raw, spec, cell):
    t = raw.get("trace")
    if not t:
        return None
    secs = t.get("programs", {}).get(PROGRAM)
    calls = t.get("program_calls", {}).get(PROGRAM)
    if not secs or not calls:
        return None
    return float("%.4g" % (1e3 * secs / calls))
