"""One module per kind of window, found by the ``kind`` of a workload file."""
