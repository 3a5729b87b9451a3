"""Beyond-paper optimizers: `localdp` (CoCoA-style additive aggregation
for non-convex data-parallel training) and `compress`, a deprecated
re-export of `repro.comm.compress`."""
