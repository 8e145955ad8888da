"""One reader per source type, found by the ``reader`` of a metric file."""
