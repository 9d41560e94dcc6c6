"""The controls of the LDA cells: faults planted under the cell's driver,
each of which ``correct`` has to refuse, and by a part that is named.

The tests plant them at a toy size on the CPU (``test_lda_check.py``);
on the chip, at the cell's own size and through the whole harness:

    python3 perf/tests/lda_faults.py <fault|none> <seed> <seconds>

prints the run's ``info`` line and its result line, as ``run.py`` does.
A fault is a function that patches the driver's class.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time

import jax.numpy as jnp
import numpy as np


def _books_redone(D):
    """Before the check, the tables are rebuilt from the chain the faulty
    sampler left, so that its books are exact ((a) and (b) hold) and its
    draws alone are judged.  The same tokens land in the same slots: the
    kept chain still reads through the driver's ``_tokens``."""
    check = D.check

    def redone(self):
        m, before = self.model, self._tokens()
        doc, word, z = m.token_state()
        m._install_pack(m.pack_tokens(doc, word, z0=z))
        for a, b in zip(before, self._tokens()):
            np.testing.assert_array_equal(a, b)
        return check(self)

    D.check = redone


def _zero_nwk(D, late: bool, redo_books: bool):
    block = D.block

    def blind(self):
        if self.sweeps >= (self.chain_sweeps if late else 0):
            self.model.Nwk = jnp.zeros_like(self.model.Nwk)
        return block(self)

    D.block = blind
    if redo_books:
        _books_redone(D)


def zero_nwk(D):
    """Every sweep samples against ``N_wk = 0`` (the word's own counts
    never reach the posterior); its books exact."""
    _zero_nwk(D, late=False, redo_books=True)


def zero_nwk_late(D):
    """The same sampler, gone wrong only after the sweep (c) is taken
    at; its books exact."""
    _zero_nwk(D, late=True, redo_books=True)


def zero_nwk_late_books_kept(D):
    """Gone wrong only after that sweep, its books as it left them: the
    word-topic table holds the deltas of its last sweep alone."""
    _zero_nwk(D, late=True, redo_books=False)


def frozen(D):
    """A sampler that returns its state unchanged and says it touched
    every token (after a wait, so that a window holds some tens of its
    blocks and not a million)."""
    setup = D.setup

    def still(self):
        setup(self)
        m = self.model

        def identity(epochs):
            time.sleep(0.05)
            m.last_work = np.asarray([float(m.n_tokens)])

        m.sample_epochs = identity

    D.setup = still


def bf16_tables(D):
    """The nearest precision below the configuration's: the word-topic
    table through bfloat16 (a store, a wire) after the window."""
    check = D.check

    def lossy(self):
        m = self.model
        m.Nwk = m.Nwk.astype(jnp.bfloat16).astype(jnp.float32)
        return check(self)

    D.check = lossy


def one_topic(D):
    """One token's topic altered where it is produced, the tables not
    told."""
    check = D.check

    def altered(self):
        m = self.model
        slot = int(self._tokens()[2][17])
        flat = m.z_grid.reshape(-1)
        new = (flat[slot] + 1) % self.data["n_topics"]
        m.z_grid = flat.at[slot].set(new).reshape(m.z_grid.shape)
        return check(self)

    D.check = altered


FAULTS = {f.__name__: f for f in (zero_nwk, zero_nwk_late,
                                  zero_nwk_late_books_kept, frozen,
                                  bf16_tables, one_topic)}


@contextlib.contextmanager
def planted(fault: str | None):
    """Every LDA driver module loaded inside carries the fault (``None``:
    the driver as it is)."""
    from perf import spec

    load = spec.load_module
    tail = os.path.join("drivers", "lda.py")

    def faulty(path):
        mod = load(path)
        if fault and path.endswith(tail):
            FAULTS[fault](mod.Driver)
        return mod

    spec.load_module = faulty
    try:
        yield
    finally:
        spec.load_module = load


def main(argv) -> int:
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from perf import harness

    fault = None if argv[0] == "none" else argv[0]
    with planted(fault):
        out = harness.run_cell(root, "lda-sweeps", int(argv[1]),
                               float(argv[2]), False)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
