"""Layer: programs. Median, over the window's ticks that launch BOTH
programs, of the time from the first launch's ``call`` returning to the
second's: the second program's build, put and call and the scheduler's
statements between the two launches. Where it exceeds the first program's
device time (``prefill_chunk_device_ms`` where the chunk program goes first)
the device idles a second time a cycle, between the chunk program and the
decode tick. ``None`` in a window where no tick launched both.
``perfbench/metrics/_launch_path.py`` says what a tick and its launches are.
Source: program_span."""

from perfbench.metrics import _launch_path


def read(outcome):
    lags = []
    for t in _launch_path.ticks(outcome):
        if len(t["launches"]) < 2:
            continue
        first, second = t["launches"][:2]
        if second["program"] != first["program"]:
            lags.append(second["call"].t1 - first["call"].t1)
    return _launch_path.median_ms(lags)
