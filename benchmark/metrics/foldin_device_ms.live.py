"""foldin_device_ms.live — device time of the fold's programs a speed-layer
cycle, in ms: the device seconds in the trace of the solve (``jit__solve_rows``:
the gather from the resident int8 table, the Gramians, the batched Cholesky)
and of the update that writes the solved rows into the resident user table
(``jit_patch_rows``), over the solve's runs. None where the trace holds no
solve (a program that folds under other names, a capture without an event).
Four significant digits."""

SOLVE, PATCH = "jit__solve_rows", "jit_patch_rows"


def read(raw, spec, cell):
    t = raw.get("trace")
    if not t:
        return None
    programs, calls = t.get("programs", {}), t.get("program_calls", {})
    if not programs.get(SOLVE) or not calls.get(SOLVE):
        return None
    return float("%.4g" % (1e3 * (programs[SOLVE] + programs.get(PATCH, 0.0)) / calls[SOLVE]))
