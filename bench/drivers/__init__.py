"""Traffic drivers: one general driver per kind of work, found by the
``driver`` key of a traffic file."""
