"""Plain references and data generators: the yardstick, independent of
``src/repro``."""
