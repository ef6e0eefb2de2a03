"""Hand counts of the paper CNN (``bench/models/cnn.py``) and of ring_agg's
needed bytes."""
import json
import os

import counts
import models

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_cnn_forward_flops_per_image():
    cfg = config("fleet-k10000")
    cnn = models.of(cfg)
    # conv1 451,584 + conv2 7,225,344 + fc1 802,816 + fc2 2,560
    assert cnn.forward_flops(cfg) == 8_482_304
    assert [2 * m for _, m, _ in cnn.layers(cfg)] == [
        451_584, 7_225_344, 802_816, 2_560]


def test_cnn_train_flops_skip_the_image_gradient():
    cfg = config("fleet-k10000")
    assert models.of(cfg).train_flops(cfg) == 3 * 8_482_304 - 451_584


def test_packed_width_is_the_lane_aligned_model():
    for name in ("fleet-k10000", "corridor-r8-k4000"):
        cfg = config(name)
        assert models.of(cfg).packed_params(cfg) == 422_016
        assert cfg["cnn"]["params_packed"] == 422_016


def test_minibatch_is_the_smallest_shard():
    assert counts.minibatch(config("fleet-k10000")["scenario"]) == 8
    assert counts.minibatch(config("corridor-r8-k4000")["scenario"]) == 9


def test_study_flops_fleet():
    cfg = config("fleet-k10000")
    want = (60 * 8 * (3 * 8_482_304 - 451_584)
            + 6 * 400 * 8_482_304)
    assert counts.study_flops(cfg, 10) == want


def test_fleet_uploads_are_bf16_rows():
    cfg = config("fleet-k10000")
    veh = list(range(60))                   # nobody downloads twice
    rd, wr = counts.ring_agg_bytes(cfg, veh, [0] * 60, 10)
    uploads = 60 * 422_016 * 2
    assert uploads == 50_641_920
    # one chain per evaluation (rounds 10, 20, ..., 60)
    assert (rd, wr) == (uploads + 6 * 4 * 422_016, 6 * 4 * 422_016)


def test_fleet_chain_splits_where_a_download_reads_the_model():
    cfg = config("fleet-k10000")
    veh = list(range(60))
    veh[30] = 3                             # vehicle 3 re-downloaded after round 3
    _, wr = counts.ring_agg_bytes(cfg, veh, [0] * 60, 10)
    assert wr == 7 * 4 * 422_016


def test_corridor_chains_end_per_rsu_at_reconciles():
    cfg = config("corridor-r8-k4000")
    veh = list(range(40))
    rsu = [r % 2 for r in range(40)]        # two RSUs take turns
    rd, wr = counts.ring_agg_bytes(cfg, veh, rsu, 10)
    # boundaries 8, 10, 16, 20, 24, 30, 32, 40: both RSUs open at each
    assert wr == 8 * 2 * 4 * 422_016
    assert rd == 40 * 422_016 * 4 + wr
