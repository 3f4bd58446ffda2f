"""host_gap_ms.serve: mean time with no operation on the device between
consecutive runs of the serve-step program while requests are live, in
milliseconds (profiler trace; liveness from the benchmark's host span)."""


def read(m):
    if m.get("kind") != "serve" or "trace" not in m:
        return None
    g = m["trace"].get("program_gaps_s") or []
    if not g:
        return None
    return 1e3 * sum(g) / len(g)
