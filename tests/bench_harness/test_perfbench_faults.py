"""With the timed path broken underneath, the harness's whole run (all
but its look for a chip) comes out not correct: once for each fault a
shuffle job can have."""

import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness
from perfbench_cells import FOUR_CHIP, ROOT, TINY, make_cell
from sparkrdma_tpu.exchange import protocol



def _wrap_exchange(monkeypatch, change):
    orig = protocol.ShuffleExchange.exchange

    def broken(self, records, *a, **kw):
        out, totals, incoming = orig(self, records, *a, **kw)
        return (*change(records, out, totals), incoming)

    monkeypatch.setattr(protocol.ShuffleExchange, "exchange", broken)


def state_unchanged(records, out, totals):
    """The exchange hands back its input as it came."""
    chips = totals.shape[0]
    return (jnp.copy(records),
            jnp.full((chips,), records.shape[1] // chips, jnp.int32))


def half_left_out(records, out, totals):
    """Half of each chip's records are left out of the output."""
    return out, totals // 2


def answer_altered(records, out, totals):
    """One word of one record is altered where the output is made."""
    return out.at[out.shape[0] - 1, 0].add(jnp.uint32(1)), totals


FAULTS = {"state_unchanged": state_unchanged,
          "half_left_out": half_left_out,
          "answer_altered": answer_altered}


def _run(cell_name):
    cell = make_cell(cell_name)
    return harness.run_cell(ROOT, cell, 99, 0.2, False,
                            jax.devices()[:cell.chips], time.perf_counter(),
                            records_per_chip=TINY[cell_name])


@pytest.mark.parametrize("cell_name", sorted(TINY))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(cell_name, fault, monkeypatch):
    _wrap_exchange(monkeypatch, FAULTS[fault])
    res = _run(cell_name)
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["checks"].values())


def test_exchange_between_chips_left_out(monkeypatch):
    """Every chip keeps the slots it meant to send to the others."""
    monkeypatch.setattr(protocol.ShuffleExchange, "_data_a2a",
                        lambda self, collective_id=7: (lambda slots: slots))
    res = _run(FOUR_CHIP)
    assert res["correct"] is False
    assert res["checks"]["misplaced_records"]["value"] > 0
