"""One driver per configuration family, named by a configuration's
``driver`` key: ``run(cell, seed, seconds, window, spans) -> Outcome``."""
