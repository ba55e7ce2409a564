"""Share of the traced window in which the device ran no operation, in
%, in the batch cells: 100 x (1 - union of the device's operation
intervals / window), from the profiler trace (``bench/trace.py``)."""


def read(rec):
    return rec["trace"].idle_pct()
