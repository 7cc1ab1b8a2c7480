"""Faults planted in the timed path, to show that ``correct`` catches them.

Each is a ``fault(engine)`` for ``bench.run.run``: it wraps one of the
engine's programs after the engine is built (the fused decode-and-sample
step, or the prefill chunk), so the window's programs run broken.
``bench/calibrate.py --fault <name>`` reads one on the chip;
``bench/tests/test_control_and_faults.py`` plants each of ``FAULTS`` at
CPU size, ``bench/tests/test_ssm_by_files.py`` the state-space one.
(Half a batch averaged away and a dropped exchange between chips are
faults of training and of sharded cells; a one-chip serving cell has
neither.)
"""
from __future__ import annotations

import jax.numpy as jnp


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


def ssm_state_dropped(engine) -> None:
    """The recurrent state of every state-space layer is zeroed at each
    prefill-chunk boundary after a prompt's first: a row that continues
    its prompt starts the chunk from no state (the convolution's shift
    register is kept)."""
    step = engine._chunk_step

    def dropped(p, caches, toks, offsets, n_new):
        later = (offsets > 0) & (n_new > 0)
        ssm = caches.ssm
        state = jnp.where(later[None, :, None, None, None], 0.0, ssm.state)
        return step(p, caches._replace(ssm=ssm._replace(state=state)),
                    toks, offsets, n_new)
    engine._chunk_step = dropped


#: faults every serving cell can have
FAULTS = {"state_unchanged": state_unchanged, "token_altered": token_altered}
#: faults of a cell whose layers carry recurrent state
STATE_SPACE_FAULTS = {"ssm_state_dropped": ssm_state_dropped}
