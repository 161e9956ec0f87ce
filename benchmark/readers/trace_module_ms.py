"""Mean duration, in milliseconds, of the program executions on the
first device plane whose ``XLA Modules`` name matches
``params["pattern"]``: the step program picked out by the name the
program gives its jitted function, not by whatever ran. Nothing where
the trace holds no such line (a rehearsal on the CPU) or no execution
of that name (a program from before the names)."""
import trace_reduce


def read(params, facts):
    if not facts["trace"].devices:
        return None
    count, seconds = trace_reduce.module_executions(
        facts["trace"].devices[0], params["pattern"])
    return 1e3 * seconds / count if count else None
