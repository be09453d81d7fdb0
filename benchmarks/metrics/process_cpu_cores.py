"""Host, all threads: CPU-seconds of the whole process (generator
included) per second of the window, from ``time.process_time()``."""


def read(rec):
    return rec["cpu_s"] / rec["window_s"]
