"""Data generators, one module each, found by the ``data.generator`` key of
a configuration file. Each has ``generate(key, cfg) -> dict`` of device
arrays."""
