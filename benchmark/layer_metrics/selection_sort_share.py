"""The share of the window's selection launches that sorted every row of
every segment to keep k of them: the server's ``selection.lowering.sort``
marks over all ``selection.lowering.first|topk|sort`` marks, one mark a
launch that carries a selection (``engine/kernel.py selection_lowering``:
``first`` without a sort column, ``topk`` where the sort columns' table
ordinals pack into one key, ``sort`` where the key space is wider).
33.3 in ``hits_search_selection_closed`` as the program stands: of the
three shapes ``by_time_phrase`` alone.  Nothing where the program has no
such counters, or the window launched no selection."""

LOWERINGS = ("first", "topk", "sort")


def read(run):
    keys = [f"server.meter.selection.lowering.{k}" for k in LOWERINGS]
    if not any(key in run.after for key in keys):
        return None
    launches = sum(run.delta(key) for key in keys)
    return 100.0 * run.delta(keys[-1]) / launches if launches else None
