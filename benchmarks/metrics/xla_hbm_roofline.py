"""XLA programs: the bytes the window's fold must move (result rows in
the window times the configuration's logical bytes per row, from its
shapes alone) over what the chip's HBM could move in the time an
operation ran on it (busy seconds of the trace times the peak for the
device kind), in percent.  A device that is not in the table is an
error."""


def read(rec):
    tr = rec["trace"]
    if tr is None or tr["busy_s"] <= 0 or not rec["rows"]:
        return None
    peak = rec["peaks"]["hbm_gb_per_s"][rec["device"]["kind"]] * 1e9
    # the rows of the host's window, scaled to the traced window
    rows = rec["rows"] * tr["window_s"] / rec["window_s"]
    busy = tr["busy_s"] * tr["n_devices"]
    return 100.0 * rows * rec["bytes_per_row"] / (busy * peak)
