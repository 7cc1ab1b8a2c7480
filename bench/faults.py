"""Faults planted in the timed path, to show that ``correct`` catches them.

Each is a ``fault(engine)`` for ``bench.run.run``: it wraps the engine's
fused decode-and-sample step after the engine is built, so the window's
programs run broken.  ``bench/calibrate.py --fault <name>`` reads one on
the chip; ``bench/tests/test_control_and_faults.py`` plants each at CPU
size.  (Half a batch averaged away and a dropped exchange between chips
are faults of training and of sharded cells; a one-chip serving cell has
neither.)
"""
from __future__ import annotations


def state_unchanged(engine) -> None:
    """The decode step returns its state, the KV cache, unchanged."""
    step = engine._serve_sample
    engine._serve_sample = lambda p, c, *a: (step(p, c, *a)[0], c)


def token_altered(engine) -> None:
    """Every token is altered where it is produced: the next id."""
    step = engine._serve_sample
    vocab = engine.model.cfg.vocab

    def altered(p, c, *a):
        toks, caches = step(p, c, *a)
        return (toks + 1) % vocab, caches
    engine._serve_sample = altered


FAULTS = {"state_unchanged": state_unchanged, "token_altered": token_altered}
