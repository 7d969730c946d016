"""Readers of the time between two decode dispatches: how long the chip
waits for the host after each one (from the device trace's idle time and
the classes `harness.fill_trace` gives it), and how many rows each
dispatch carried (the `rows` the program puts on its `lm.decode_step`
span). What they read is there on the parent commit too; a run without a
trace, a dispatch or such spans reads as None."""


def dispatch_gap_ms(run):
    """The device's idle seconds in the traced window less those the
    loop spent with no row to run (`loop.idle_wait`) and those between
    the operations of one program (`device.between_ops`), over the decode
    dispatches (`jit_run`'s runs) there: what a dispatch waits for the
    host, on average."""
    runs = run.modules.get("jit_run", (0.0, 0))[1]
    if not runs or not run.trace_window_s:
        return None
    gaps = dict(run.breakdown.get("idle_gaps", ()))
    idle = (run.trace_window_s - run.busy_s - gaps.get("loop.idle_wait", 0.0)
            - gaps.get("device.between_ops", 0.0))
    return max(idle, 0.0) * 1e3 / runs


def dispatch_occupancy(run):
    """Rows a decode dispatch carried over slots, averaged over the
    `lm.decode_step` spans that end in the window. A row admitted in a step
    joins the next step's dispatch, so this is at most `batch_occupancy`,
    which counts a step's rows at its end."""
    rows = [s["attrs"]["rows"] for s in run.spans
            if s["name"] == "lm.decode_step" and "rows" in s["attrs"]
            and s["t_end"] is not None and run.w0 <= s["t_end"] < run.w1]
    if not rows:
        return None
    return sum(rows) / len(rows) / run.cfg["serving"]["slots"]
