"""Entry: seconds round ``PipeGraph(...)`` ... ``start()`` returning (the
benchmark's clock): graph build, fusion, placement, lowering."""


def read(rec):
    return rec["graph_start_s"]
