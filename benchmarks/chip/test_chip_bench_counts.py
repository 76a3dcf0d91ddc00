"""Operation and byte counts against hand counts, and the peak table."""
import json
import os

import jax
import pytest

from chip import cells, flops

HERE = os.path.dirname(os.path.abspath(__file__))
TESTDATA = os.path.join(HERE, "testdata")


def _config(name):
    with open(os.path.join(TESTDATA, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_flops_of_the_tiny_decoder_match_a_hand_count():
    cfg = _config("tiny-lm")       # L=2, d=64, 2 heads of 32, ff=128, V=256
    # per layer: q,k,v,o projections 2*64*32*(2+2*2) + 2*2*32*64 = 32768;
    # gated MLP 2*3*64*128 = 49152; causal attention at S=32 sees
    # (32+1)/2 = 16.5 positions: 2*2*2*32*16.5 = 4224
    assert flops.body_flops_per_token(cfg, 32) == 2 * (32768 + 49152 + 4224)
    assert flops.head_flops_per_token(cfg) == 2 * 64 * 256
    assert flops.train_flops(cfg, 32, 10, 10) == 3 * 10 * (172288 + 32768)


def test_flops_of_the_tiny_encoder_count_the_masked_head_only():
    cfg = _config("tiny-mlm")
    # bidirectional: every query sees all 32 positions: 2*2*2*32*32 = 8192
    assert flops.body_flops_per_token(cfg, 32) == 2 * (32768 + 49152 + 8192)
    assert flops.train_flops(cfg, 32, 100, 15) == 3 * (
        100 * 180224 + 15 * 32768)


@pytest.mark.parametrize("name", ["tiny-lm", "tiny-mlm"])
def test_param_count_matches_the_program(name):
    cfg = _config(name)
    per_layer = 2 * 64 + 64 * 32 * 6 + 2 * 32 * 64 + 3 * 64 * 128
    want = 2 * per_layer + 256 * 64 + 64
    if not cfg["causal"]:
        want += 64 * 256 + 64          # untied head, mask embedding
    assert flops.param_count(cfg) == want
    cell = cells.Cell(name=name, chips=1, n_nodes=4, model=cfg, traffic={},
                      workload={}, entry={"config": name})
    from repro.models.model import make_model
    shapes = jax.eval_shape(lambda k: make_model(
        cells.model_config(cell)).init(k)[0], jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == want


def test_round_bytes_read_and_write_the_stacked_state_once():
    # 4 nodes x 98,624 float32 parameters, read once and written once
    assert flops.round_bytes(4, 98624, 4) == 2 * 4 * 98624 * 4
    assert flops.round_bytes(4, 98624, 2) == 2 * 4 * 98624 * 2


def test_published_configs_count_their_stated_parameters():
    for c in cells.benchmark()["configs"]:
        with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                               c["file"])) as f:
            cfg = json.load(f)
        assert flops.param_count(cfg) == cfg["params_per_node"]


def test_peaks_of_a_known_kind_and_an_unknown_kind_fails():
    p = flops.load_peaks("TPU v5 lite")
    assert p == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="no peaks"):
        flops.load_peaks("TPU v99")
