"""The comparison that decides ``correct``.

Every packet the window admitted is judged, after the window has closed
and the program's state is freed:

* ``lost``: admitted (offered and not tail-dropped) but never retired;
* ``duplicated``: retired more than once, or retired without being
  offered;
* ``wrong_slot``: the program's slot differs from sigma over reg0;
* ``wrong_verdict``: the verdict differs from the reference's sign,
  among packets whose reference score lies beyond the rounding margin;
* ``wrong_action``: Pi's action differs from the reference's action
  (for an undecided score, from Pi of the program's own verdict).

Each is an exact count with the limit 0.  Packets are remade from the
seed and their stamps, and each is scored under the model its slot held
at the tick that served it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import bnn

LIMITS = {"lost": 0, "duplicated": 0, "wrong_slot": 0, "wrong_verdict": 0,
          "wrong_action": 0}
BLOCK = 4096


@dataclasses.dataclass
class Served:
    """What the harness kept of the timed packets, one row per retirement."""
    seqs: np.ndarray       # stamp
    slots: np.ndarray      # program's slot
    verdicts: np.ndarray   # program's verdict
    actions: np.ndarray    # program's action
    models: np.ndarray     # index into the stacked models the slot held


def judge(served: Served, *, offered: int, dropped: np.ndarray, source,
          models: dict, num_slots: int, meta_words: int,
          block: int = BLOCK) -> dict:
    """Readings of every number in ``LIMITS`` (plus ``checked`` and
    ``undecided``, which are not compared) for timed stamps
    ``0 .. offered - 1`` of which ``dropped`` were tail-dropped."""
    seqs = served.seqs.astype(np.int64)
    order = np.argsort(seqs, kind="stable")
    seqs = seqs[order]
    first = np.ones(seqs.shape[0], bool)
    first[1:] = seqs[1:] != seqs[:-1]
    valid = (seqs >= 0) & (seqs < offered)
    keep = order[first & valid]
    admitted = offered - np.unique(dropped).shape[0]
    out = {"checked": int(keep.shape[0]),
           "lost": int(admitted - keep.shape[0]),
           "duplicated": int(seqs.shape[0] - keep.shape[0])}
    if keep.shape[0] == 0:
        out.update(wrong_slot=0, wrong_verdict=0, wrong_action=0, undecided=0)
        return out

    w1 = bnn.layer1_table(models)
    b1, w2, b2 = (jnp.asarray(models[k]) for k in ("b1", "w2", "b2"))
    pending = []
    for i in range(0, keep.shape[0], block):
        idx = keep[i:i + block]
        rows = source.packets(served.seqs[idx].astype(np.int64))
        n = rows.shape[0]
        pad = block - n
        dev_rows = np.concatenate([rows, np.repeat(rows[:1], pad, 0)])
        model = np.concatenate([served.models[idx],
                                np.repeat(served.models[idx][:1], pad)])
        y, margin = bnn.scores(jnp.asarray(dev_rows),
                               jnp.asarray(model, jnp.int32), w1, b1, w2, b2,
                               meta_words=meta_words)
        pending.append((idx, rows[:, :3].copy(), y, margin, n))  # sigma, Pi
    wrong_slot = wrong_verdict = wrong_action = undecided = 0
    for idx, rows, y, margin, n in pending:
        y = np.asarray(jax.device_get(y))[:n]
        margin = np.asarray(jax.device_get(margin))[:n]
        decided = np.abs(y) > margin
        ref_verdict = y > 0
        verdicts = served.verdicts[idx].astype(bool)
        actions = served.actions[idx].astype(np.int32)
        wrong_slot += int(np.sum(served.slots[idx] != bnn.sigma(rows, num_slots)))
        wrong_verdict += int(np.sum(decided & (verdicts != ref_verdict)))
        want = np.where(decided, bnn.pi(ref_verdict, rows), bnn.pi(verdicts, rows))
        wrong_action += int(np.sum(actions != want))
        undecided += int(np.sum(~decided))
    out.update(wrong_slot=wrong_slot, wrong_verdict=wrong_verdict,
               wrong_action=wrong_action, undecided=undecided)
    return out


def control_served(served: Served, *, source, models: dict, num_slots: int,
                   meta_words: int, block: int = BLOCK) -> Served:
    """The control's answers in the program's place: the reference with
    layer 2 in bfloat16, for the same packets and models."""
    w1 = bnn.layer1_table(models)
    b1, w2, b2 = (jnp.asarray(models[k]) for k in ("b1", "w2", "b2"))
    n_all = served.seqs.shape[0]
    slots = np.zeros(n_all, np.int64)
    verdicts = np.zeros(n_all, bool)
    actions = np.zeros(n_all, np.int32)
    for i in range(0, n_all, block):
        sl = slice(i, i + block)
        rows = source.packets(served.seqs[sl].astype(np.int64))
        n = rows.shape[0]
        pad = block - n
        model = np.concatenate([served.models[sl],
                                np.repeat(served.models[sl][:1], pad)])
        y, _ = bnn.scores(
            jnp.asarray(np.concatenate([rows, np.repeat(rows[:1], pad, 0)])),
            jnp.asarray(model, jnp.int32), w1, b1, w2, b2,
            meta_words=meta_words, layer2="bfloat16")
        v = np.asarray(y)[:n] > 0
        slots[sl] = bnn.sigma(rows, num_slots)
        verdicts[sl] = v
        actions[sl] = bnn.pi(v, rows)
    return dataclasses.replace(served, slots=slots, verdicts=verdicts,
                               actions=actions)
