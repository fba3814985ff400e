"""The program's Pallas kernels as the device trace shows them.

A kernel is an operation whose HLO is a ``tpu_custom_call``. Its operation
name is that of the jitted wrapper around the ``pallas_call`` (for example
``%distance_min_update_gated_pallas.12``), so a layer's kernels are found
by those name prefixes. The IVF scan's custom call carries no such name
(``%closed_call.N``); it is the kernel inside the program of
``kernels/ivf_scan.py``'s wrapper, ``jit_ivf_scan_pallas``.
"""
SEEDING = ("seed_prologue", "distance_min_update", "row_min_d2", "tile_cap")
LLOYD = ("lloyd_assign",)
SCAN_PROGRAM = "jit_ivf_scan_pallas"
