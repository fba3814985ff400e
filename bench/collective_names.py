"""The operations that cross chips, as the device trace names them: the
HLO collectives, whose operation names start with these (an async
collective shows as its ``-start`` and ``-done`` halves)."""
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")
