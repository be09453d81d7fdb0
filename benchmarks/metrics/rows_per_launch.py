"""Dispatch: result rows the sink received in the window over device
launches in it: how many windows one launch's fixed cost is spread
over."""


def read(rec):
    if not rec["launches"]:
        return None
    return rec["rows"] / rec["launches"]
