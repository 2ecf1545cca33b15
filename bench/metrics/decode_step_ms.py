"""decode_step_ms: device time of one execution of the jitted decode
program, from its module events in the trace."""
from bench.trace import serve_programs


def read(view):
    decode, _ = serve_programs(view.trace)
    if not decode:
        return None
    return 1e3 * sum(e.dur for e in decode) * 1e-9 / len(decode)
