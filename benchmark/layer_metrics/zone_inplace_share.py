"""The share of the window's zone-tier launches whose program read the
candidate blocks where they are staged: the server's
``zone.blocks.inplace`` marks over ``zone.blocks.inplace`` +
``zone.blocks.gathered``, one mark a launch of a block-skipping program
(``engine/kernel.py zone_blocks``).  100 says every such launch looped
over its block ids in place; 0 that every one copied the candidate
blocks out first (a selection, distinct pairs, a sorted HLL).  Nothing
where the program has no such counters, or the window launched no zone
program."""

FORMS = ("inplace", "gathered")


def read(run):
    keys = [f"server.meter.zone.blocks.{form}" for form in FORMS]
    if not any(key in run.after for key in keys):
        return None
    launches = sum(run.delta(key) for key in keys)
    return 100.0 * run.delta(keys[0]) / launches if launches else None
