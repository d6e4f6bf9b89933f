"""Share of the HBM roofline one decode step reaches: the least bytes a
step moves (every weight once in bf16, the configuration's compute type,
plus the KV cache at the active positions; counts.decode_step_bytes)
over the HBM peak, divided by the step's device time from the trace.
The program stores its weights in float32, so it moves about twice the
weight bytes counted here."""
import counts


def read(run):
    if run.trace is None or run.decode_module is None or run.peak is None:
        return None
    m = run.trace["modules"].get(run.decode_module)
    if not m or not m["launches"]:
        return None
    lo = run.t_end - run.trace["window_s"]
    ctx = [st[3] for st in run.steps if st[0] >= lo and st[2]]
    if not ctx:
        return None
    need = counts.decode_step_bytes(run.dims, sum(ctx) / len(ctx))
    t = m["seconds"] / m["launches"]
    return need / run.peak["hbm_bytes_per_s"] / t * 100.0
