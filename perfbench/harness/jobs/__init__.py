"""Job kinds: one module per kind, found by the ``job`` key of a cell file."""
