"""`chip_smoke.control_plane` on the CPU, at a tiny cut of its deployment.

The smoke's control-plane phase builds the benchmark's deployment through
``bench.fleet`` and replays the benchmark's traffic.  Here it runs at 300
streams over 3 cells with one 32-event block, the size ``bench/tests/``
gives its own harness, with every check of the phase as on the chip.
"""
import json

import chip_smoke

SIZE = {"streams": 300, "cells": 3}


def test_control_plane_phase_passes_every_check_at_a_tiny_size():
    cfg = {**json.loads(chip_smoke.CONFIG.read_text()), **SIZE}
    traffic = json.loads(chip_smoke.TRAFFIC.read_text())
    smoke = chip_smoke.Smoke()
    rec = {"phase": "control_plane"}
    chip_smoke.control_plane(smoke, rec, cfg, traffic, seed=0, blocks=1)
    assert smoke.failures == [], rec
    assert rec["checks"] and all(rec["checks"].values()), rec["checks"]
    assert (rec["streams"], rec["cells"]) == (300, 3)
    assert rec["replay_events"] == sum(traffic["mix"].values()) == 32
    assert rec["replay_delta"] == 0.0
    assert rec["pricing_dispatches_mismatched"] == 0
