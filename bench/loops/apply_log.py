"""Closed loop over ``FmmSolver.apply`` for the logarithmic kernel: the
loop of ``apply.py`` (one problem per call, ``problems`` distinct inputs
made in set-up, ``overflow`` read from the program's own connectivity),
with a check on Re phi.

Im phi of the logarithmic kernel is defined up to multiples of 2 pi q:
the FMM's expansions and the direct sum take the logarithm's branch at
different points, so Im phi differs from the direct sum's by such
multiples. With real charges Re phi = sum q log|y - x| is single-valued,
and ``err_norm`` is max |Re phi - Re ref| / max |Re ref| over the
checked targets, against the float64 direct sum of ``bench/reference.py``.
The check does not see Im phi, nor so the program's ``atan2``;
``chip_smoke.py``'s log phase compares complex phi with the reference
backend's on the chip.
"""
from __future__ import annotations

import contextlib

from bench import reference
from bench.loops.apply import Loop as ApplyLoop

#: Terms in a block of ``bench.reference``'s direct sum here: one target
#: over 2**20 sources, so each of a block's seven complex128 temporaries
#: is 16 MiB, which glibc serves from its arenas and reuses. The
#: reference's own 2**21 (two targets, 32 MiB temporaries, above glibc's
#: largest mmap threshold) maps fresh memory for every temporary, about
#: 30 GiB over a checked call of 256 targets, which the one-chip machine
#: gave back too slowly: two checked calls lifted its host memory past
#: 36 of its 40 GiB. The sums are the same either way: a block's rows are
#: summed one by one.
BLOCK_TERMS = 1 << 20


@contextlib.contextmanager
def _reference_blocks(terms: int):
    before = reference._BLOCK_TERMS
    reference._BLOCK_TERMS = terms
    try:
        yield
    finally:
        reference._BLOCK_TERMS = before


class Loop(ApplyLoop):

    def _errors(self, phi_at) -> float:
        errs = []
        for key, phi, z, q in self.samples():
            t = self._targets(len(z), key)
            if key not in self._refs:
                with _reference_blocks(BLOCK_TERMS):
                    self._refs[key] = reference.direct_potential(
                        z[t], z, q, self.cfg.kernel)
            errs.append(reference.error_normwise(
                phi_at(phi, z, q, t).real, self._refs[key].real))
        return max(errs)
