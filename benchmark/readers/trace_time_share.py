"""Share of the device's operation time spent in the operations whose
HLO text matches ``params["pattern"]``, on the first device plane."""
import trace_reduce


def read(params, facts):
    if not facts["trace"].devices:
        return None
    ops = facts["trace"].devices[0].ops
    total = trace_reduce.total_op_seconds(ops)
    if total <= 0:
        return None
    return 100.0 * trace_reduce.time_matching(ops, params["pattern"]) / total
