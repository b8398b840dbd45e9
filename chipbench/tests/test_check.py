"""The comparison that decides ``correct`` fails where it must.

The control -- the reference with layer 2 in bfloat16 put in the
program's place -- and faults planted in the timed path underneath a
rehearsed run (the harness's look for a chip skipped) must each come out
not correct; the program as it is must come out correct.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell

SEED = 2**31 + 77


def _run(name, seconds=0.4, **kw):
    return cell.run_cell(name, SEED, seconds, False, t_proc=time.perf_counter(),
                         rehearse=True, **kw)


@pytest.fixture(autouse=True)
def _fresh_jit_caches():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_program_correct_and_control_not():
    out = _run("h32-k16-q4.saturate", control=True)
    assert out["result"]["correct"]
    assert out["readings"]["wrong_verdict"] == 0
    assert out["readings"]["checked"] > 10_000
    assert out["control"]["wrong_verdict"] > 0


def test_answer_altered_where_produced(monkeypatch):
    from repro.core import pipeline
    real = pipeline.packet_step

    def flipped(*a, **kw):
        r = real(*a, **kw)
        return r._replace(verdicts=r.verdicts.at[0].set(~r.verdicts[0]))

    monkeypatch.setattr(pipeline, "packet_step", flipped)
    out = _run("h32-k16-q4.saturate")
    assert out["readings"]["wrong_verdict"] > 0
    assert not out["result"]["correct"]


def test_action_altered_where_produced(monkeypatch):
    from repro.core import pipeline
    real = pipeline.packet_step

    def forward_all(*a, **kw):
        r = real(*a, **kw)
        return r._replace(actions=jnp.zeros_like(r.actions))

    monkeypatch.setattr(pipeline, "packet_step", forward_all)
    out = _run("h32-k1-q4.saturate")
    assert out["readings"]["wrong_action"] > 0
    assert not out["result"]["correct"]


def test_slot_misread(monkeypatch):
    from repro.core import packet

    def off_by_one(packets, num_slots):
        raw = packets[..., packet.SLOT_WORD].astype(jnp.int32)
        return jnp.clip(raw + 1, 0, num_slots - 1)

    monkeypatch.setattr(packet, "slot_of", off_by_one)
    out = _run("h32-k16-q4.saturate")
    assert out["readings"]["wrong_slot"] > 0
    assert not out["result"]["correct"]


@pytest.mark.parametrize("fault", ["lost", "duplicated"])
def test_packet_lost_or_duplicated_in_the_ring(monkeypatch, fault):
    import numpy as np
    from repro.dataplane import ring
    real = ring.PacketRing.pop

    def pop(self, max_n):
        rows, ts = real(self, max_n)
        if rows.shape[0] > 1:
            if fault == "lost":
                rows, ts = rows[1:], ts[1:]
            else:
                rows = np.concatenate([rows[:1], rows[1:]])
                rows[1] = rows[0]
        return rows, ts

    monkeypatch.setattr(ring.PacketRing, "pop", pop)
    out = _run("h32-k16-q4.saturate")
    assert out["readings"][fault] > 0
    assert not out["result"]["correct"]


#: The open-loop swap cell is not in ``BENCHMARK.json`` yet; its traffic
#: file and generator are, and this test drives them through the harness.
SWAP_CELL = {"name": "h32-k16-q4.swap", "config": "h32-k16-q4",
             "traffic": "swap", "chips": 1}


def test_swap_never_published(monkeypatch):
    from repro.core import bank
    real_spec = cell.load_spec

    def spec_with_swap_cell():
        spec = real_spec()
        if all(w["name"] != SWAP_CELL["name"] for w in spec["workloads"]):
            spec["workloads"].append(SWAP_CELL)
        return spec

    monkeypatch.setattr(cell, "load_spec", spec_with_swap_cell)
    real_commit = bank.DoubleBufferedBank.commit
    calls = {"n": 0}

    def commit(self):
        calls["n"] += 1
        if calls["n"] <= 2:          # the warm-up's two reinstalls
            return real_commit(self)
        self._staged.clear()
        self._staged_epoch = None
        return self.active

    monkeypatch.setattr(bank.DoubleBufferedBank, "commit", commit)
    out = _run("h32-k16-q4.swap", seconds=0.3)
    assert out["readings"]["wrong_verdict"] > 0
    assert not out["result"]["correct"]
