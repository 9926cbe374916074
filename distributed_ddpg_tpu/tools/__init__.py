"""Offline analysis tooling for the JSONL/JSON artifacts the trainer and
the analysis gates emit. Pure stdlib — importing this package must never initialize
JAX (the CLIs run on laptops and in CI gates where no accelerator, and no
accelerator wait, is acceptable)."""
