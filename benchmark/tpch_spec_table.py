"""The table of ``tpch_lineitem_spec_1chip``: the lineitem segment of
``tpch_lineitem_1chip``, from the program's own generator and unchanged,
for a program that can read the deployment's queries.

The deployment is TPC-H Q1 and Q6 as the specification writes them, with
arithmetic inside an aggregate.  A program that cannot parse that answers
every query with a parse error in a reply of status 200, which
``run.py``'s warm-up takes for an answer (it looks at the transport
alone): the run would load the table, time a window of error replies and
exit 0 with ``correct: false``, nothing staged and nothing measured.  So
the precondition is held here, before the first segment is made, and a
program without the grammar fails the cell at once and with its reason
(``PERF.md`` section 7 asks a ``benchmark`` PR to hold the warm-up's
replies to ``exceptions`` instead, after which this check can go).
"""
PROBE = "SELECT sum(l_extendedprice*(1-l_discount)) FROM lineitem"


def segment(num_rows: int, seed: int = 7, name: str = "li0"):
    from pinot_tpu.pql import PqlParseError, parse_pql
    from pinot_tpu.tools.datagen import synthetic_lineitem_segment

    try:
        parse_pql(PROBE)
    except PqlParseError as e:
        raise RuntimeError(f"tpch_lineitem_spec_1chip needs arithmetic inside an aggregate, and this program "
                           f"cannot parse {PROBE!r}: {e}") from e
    return synthetic_lineitem_segment(num_rows, seed=seed, name=name)
