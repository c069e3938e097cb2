"""repro — near-linear l1,inf projection (arXiv 2307.09836) grown into a
sharded JAX training/serving stack. See DESIGN.md for the layer map."""
