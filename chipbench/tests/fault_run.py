"""One CPU rehearsal of a cell with a fault planted in the timed path.

    python chipbench/tests/fault_run.py --fault <name> -- <run.py arguments>

The fault is planted in the program (or its output) before ``run.py``'s
``main`` drives the cell, so everything after it -- the window, the
reference and the comparison -- runs as in a real run.  Faults:

- ``none``: nothing planted;
- ``state_unchanged``: the PDE block returns its input field; the serving
  decode step returns the KV pool it was given (nothing written);
- ``answer_altered``: one cell of each PDE block's output is changed;
- ``token_altered``: one slot's decoded token is changed where the decode
  step produces it, a different slot each step, so that every request
  carries some altered tokens;
- ``half_batch``: the upper half of each decode step's live rows (rounded
  up, so a lone row too) returns its input token instead of a computed
  one;
- ``exchange_left_out``: each chip wraps its own halo instead of
  exchanging it with its neighbours.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro.pde import mpdata, stencil
    from repro.serve import engine

    if fault == "none":
        return
    if fault in ("state_unchanged", "answer_altered"):
        make = mpdata.make_solver

        def make_bad(*a, **kw):
            run = make(*a, **kw)
            if fault == "state_unchanged":
                return lambda psi: psi
            return lambda psi: run(psi).at[3, 5].add(0.5)
        mpdata.make_solver = make_bad
    if fault == "exchange_left_out":
        def local(field, cart, halo=1, **kw):
            lone = type("Lone", (), {"ndims": 2, "dims": (1, 1)})()
            return stencil.halo_exchange_2d(field, lone, halo)
        mpdata.halo_exchange_2d = local
    if fault in ("state_unchanged", "token_altered", "half_batch"):
        init = engine.ContinuousEngine.__init__

        def init_bad(self, *a, **kw):
            init(self, *a, **kw)
            good = self._decode
            calls = [0]

            def bad(p, td, c, g):
                if fault == "state_unchanged":
                    kept = jax.tree.map(jnp.copy, c)
                    toks, _ = good(p, td, c, g)
                    return toks, kept
                toks, c = good(p, td, c, g)
                if fault == "token_altered":
                    i = calls[0] % toks.shape[0]
                    calls[0] += 1
                    toks = toks.at[i].set((toks[i] + 1) % self.cfg.vocab_size)
                else:
                    live = td[:, 1] >= 0
                    rank = jnp.cumsum(live) - 1
                    left_out = live & (rank >= live.sum() // 2)
                    toks = jnp.where(left_out, td[:, 0], toks)
                return toks, c
            self._decode = bad
        engine.ContinuousEngine.__init__ = init_bad


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", required=True)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    plant(args.fault)
    from chipbench import run
    rest = [a for a in args.rest if a != "--"]
    run.main(rest + ["--rehearse"])


if __name__ == "__main__":
    main()
