"""Reader of the decode step's context ladder: how far along the slot
cache's token axis the window's decode steps read, from the counters
`DecodeServer.stats()` keeps of it. A program that has no such counters
(the parent of the PR that brought them; a stack that brings its own step)
reads as None."""


def context_read_share(run):
    """Tokens of the slot cache's token axis that the window's decode steps
    read over the tokens it holds, a token a slot a step
    (`decode_context_read` / `decode_context_held`). 1.0 means every step
    read all of `max_len`, whatever was live."""
    if "decode_context_held" not in run.stats1:
        return None
    held = (run.stats1["decode_context_held"]
            - run.stats0.get("decode_context_held", 0))
    read = (run.stats1["decode_context_read"]
            - run.stats0.get("decode_context_read", 0))
    return read / held if held else None
