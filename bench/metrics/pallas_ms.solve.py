"""pallas_ms.solve: device time per call of all Pallas kernels together
(ops whose ``tf_op`` passes through ``pallas_call``: leaf classify, fused
M2L, P2L and fused evaluation), in ms, from the profiler trace."""


def read(run):
    if run.trace is None or run.unit != "call":
        return None
    pallas_s = run.trace.kind_s.get("pallas")
    if not pallas_s:
        return None
    return 1e3 * pallas_s / len(run.unit_s)
