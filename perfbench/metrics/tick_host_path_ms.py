"""Layer: routing and scheduling. Median, over the window's ticks, of the
time from the end of ``engine.collect.wait`` (the tick's tokens are on the
host: the device has nothing queued) to the RETURN of the first launch's
``engine.*.call``: the whole host path an idle device waits for, by the
program's clock. The inside twin of ``tick_host_ms`` (the device's idle time
a tick, by the trace): their difference is the runtime's share, from the
call's return to the program's first operation and from the tick's last
operation to the wait's return, which no host-path change short of a second
tick in flight recovers. By construction it is ``tick_exposed_host_ms``'
interval (which holds the first launch's ``build``) plus that launch's
``put`` and ``call``. ``perfbench/metrics/_launch_path.py`` says what a tick
and its first launch are. Source: program_span."""

from perfbench.metrics import _launch_path


def read(outcome):
    return _launch_path.median_ms(
        [t["launches"][0]["call"].t1 - t["wait_end"]
         for t in _launch_path.ticks(outcome) if t["launches"]])
