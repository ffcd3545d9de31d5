"""One reader per per-layer metric: ``bench/metrics/<metric>.py`` defines
``read(reading) -> float | None``, and returns None where the run holds
nothing to read.  Readers shared by split names live in ``common.py``."""
