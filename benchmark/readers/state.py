"""Reader of how much of the slots' scan state the decode steps moved, from
the counters `DecodeServer.stats()` keeps of it (`state_rows_moved`,
`state_rows_held`: a slot, a Mamba-2 layer, a decode step). A program that
keeps no such counters (the parent of the PR that brought them; a stack
without a Mamba-2 layer) reads as None."""


def state_moved_share(run):
    """Scan states the window's decode steps read and wrote over those the
    slots hold, a slot a layer a step. 1.0 means every step moved every
    slot's state, live or not; a step that moves the live rows' alone
    reads the dispatches' occupancy."""
    if "state_rows_held" not in run.stats1:
        return None
    held = (run.stats1["state_rows_held"]
            - run.stats0.get("state_rows_held", 0))
    moved = (run.stats1["state_rows_moved"]
             - run.stats0.get("state_rows_moved", 0))
    return moved / held if held else None
