"""The table of ``ssb_lineorder_flat_1chip``: ``lineorder_flat`` from the
program's own generator, a segment a contiguous range of order dates,
for a program that has the generator.

``tools/datagen.synthetic_lineorder_flat_segment`` makes the ``k``-th of
``segments`` equal parts of SSB's 2,406 days; the deployment's count of
parts is its configuration's (``configs/ssb_lineorder_flat_1chip.json``,
``segments``), read here once, so that a run at a cut size (tier-1's
rehearsal, ``benchmark/tests``) still makes the deployment's segments:
its first ones, the first dates.  A program without the generator fails
the cell on the missing name before the first segment is made.
"""
import json
import os

_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs", "ssb_lineorder_flat_1chip.json")
with open(_CONFIG) as f:
    SEGMENTS = json.load(f)["segments"]


def segment(num_rows: int, seed: int = 7, name: str = "lof0"):
    from pinot_tpu.tools.datagen import synthetic_lineorder_flat_segment

    return synthetic_lineorder_flat_segment(num_rows, seed=seed, name=name, segments=SEGMENTS)
