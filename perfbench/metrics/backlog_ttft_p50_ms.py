"""Layer: routing and scheduling. Median time from submit to first token
of the requests whose first token fell in the window. Under a backlog this
is queue wait by construction: recorded, decides nothing. Source:
host_clock."""

import statistics


def read(outcome):
    ttfts = outcome["counters"]["ttfts"]
    return 1e3 * statistics.median(ttfts) if ttfts else None
