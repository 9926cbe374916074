"""Learner math (losses, Adam, Polyak, kernels) and the numpy-only pieces
actor workers use (noise, support_auto). Import the submodule you need:
this package imports nothing itself, so a worker that takes `ops.noise`
does not pull JAX in with it."""
