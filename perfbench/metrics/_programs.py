"""Shared by the readers of program times: device durations of the
executions of the programs with one label, in the traced window."""


def durations(outcome, label: str) -> list:
    tr = outcome["trace"]
    labels = tr.get("labels", {})
    return [d for name, ds in tr["modules"].items()
            if labels.get(name) == label for d in ds]
