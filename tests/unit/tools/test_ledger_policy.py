"""Standing --diff-ledger policy: when the tree carries two or more
committed per-round program ledgers (``ledger_r*.jsonl``), the newest pair
must not show compile-cost regressions on the stable fields — flops,
bytes_accessed, peak_hbm_bytes, comm_bytes. measured_ms is deliberately
excluded from
the gate: a wall time varies from process to process and would flake
tier-1.

With fewer than two round ledgers the policy test auto-skips; the unit
tests below keep the machinery itself covered either way.
"""

import json
import os

import pytest

from deepspeed_tpu.telemetry.ledger import (
    DIFF_FIELDS,
    diff_ledgers,
    find_round_ledgers,
    load_rows,
)

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))

# the gate's field set: DIFF_FIELDS minus wall time
POLICY_FIELDS = tuple(f for f in DIFF_FIELDS if f != "measured_ms")


def _write_ledger(path, rows):
    with open(path, "w") as f:
        for name, fields in rows.items():
            rec = {"kind": "program", "program": name}
            rec.update(fields)
            f.write(json.dumps(rec) + "\n")


# ------------------------------------------------------------- machinery


def test_find_round_ledgers_orders_by_round(tmp_path):
    sub = tmp_path / "benchmarks"
    sub.mkdir()
    _write_ledger(str(tmp_path / "ledger_r10.jsonl"), {})
    _write_ledger(str(sub / "ledger_r9.jsonl"), {})
    _write_ledger(str(tmp_path / "ledger_r11.jsonl"), {})
    found = find_round_ledgers(str(tmp_path))
    names = [os.path.basename(p) for p in found]
    assert names == ["ledger_r9.jsonl", "ledger_r10.jsonl",
                     "ledger_r11.jsonl"]


def test_find_round_ledgers_empty(tmp_path):
    assert find_round_ledgers(str(tmp_path)) == []


def test_diff_fields_subset_excludes_measured_ms(tmp_path):
    old = str(tmp_path / "ledger_r1.jsonl")
    new = str(tmp_path / "ledger_r2.jsonl")
    _write_ledger(old, {"train:train_batch":
                        {"flops": 100.0, "measured_ms": 10.0}})
    _write_ledger(new, {"train:train_batch":
                        {"flops": 101.0, "measured_ms": 30.0}})
    full = diff_ledgers(load_rows(old), load_rows(new))
    assert any(e["field"] == "measured_ms" for e in full["regressions"])
    gated = diff_ledgers(load_rows(old), load_rows(new),
                         fields=POLICY_FIELDS)
    assert gated["regressions"] == []


def test_diff_fields_subset_still_gates_flops(tmp_path):
    old = str(tmp_path / "ledger_r1.jsonl")
    new = str(tmp_path / "ledger_r2.jsonl")
    _write_ledger(old, {"v2:decode": {"flops": 100.0}})
    _write_ledger(new, {"v2:decode": {"flops": 200.0}})
    out = diff_ledgers(load_rows(old), load_rows(new), fields=POLICY_FIELDS)
    assert [e["field"] for e in out["regressions"]] == ["flops"]


def test_diff_fields_gate_comm_bytes(tmp_path):
    """comm_bytes is in the policy gate: a collective-volume regression
    (the ZeRO-drift class tpucomms exists for) fails the diff like a
    flops regression would. Rows WITHOUT the field (pre-r11 ledgers) are
    skipped — the field is append-only."""
    assert "comm_bytes" in POLICY_FIELDS
    old = str(tmp_path / "ledger_r1.jsonl")
    new = str(tmp_path / "ledger_r2.jsonl")
    _write_ledger(old, {"train:train_batch": {"comm_bytes": 1000},
                        "v2:decode": {"flops": 100.0}})
    _write_ledger(new, {"train:train_batch": {"comm_bytes": 3000},
                        "v2:decode": {"flops": 100.0,
                                      "comm_bytes": 64}})
    out = diff_ledgers(load_rows(old), load_rows(new), fields=POLICY_FIELDS)
    assert [(e["program"], e["field"]) for e in out["regressions"]] == \
        [("train:train_batch", "comm_bytes")]


# ----------------------------------------------------------- the policy


def test_round_ledger_policy():
    """Diff the two newest committed round ledgers in-process; fail on any
    regression of the stable compile-cost fields."""
    ledgers = find_round_ledgers(REPO_ROOT)
    if len(ledgers) < 2:
        pytest.skip(f"{len(ledgers)} round ledger(s) committed — the "
                    "policy needs two to diff")
    old_path, new_path = ledgers[-2], ledgers[-1]
    out = diff_ledgers(load_rows(old_path), load_rows(new_path),
                       fields=POLICY_FIELDS)
    assert not out["regressions"], (
        f"compile-cost regressions {os.path.basename(old_path)} -> "
        f"{os.path.basename(new_path)}: {out['regressions']} — if "
        "intentional, regenerate the newest ledger_r*.jsonl with the "
        "accepted costs")
