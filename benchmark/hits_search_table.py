"""The table of ``clickbench_hits_search_1chip``: the ``hits`` segment of
ClickBench's search-phrase queries, from the program's own generator and
unchanged (``pinot_tpu.tools.datagen:synthetic_hits_search_segment``).  A
program without that generator fails the cell at once, on the name, before
a segment is made.
"""


def segment(num_rows: int, seed: int = 7, name: str = "hits0"):
    from pinot_tpu.tools.datagen import synthetic_hits_search_segment

    return synthetic_hits_search_segment(num_rows, seed=seed, name=name)
