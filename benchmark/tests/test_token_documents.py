"""The ``token_documents`` family and its cell on the CPU: the rehearsal of
``sdar-30b-a3b-chat.train-blockdiff-docs`` comes out ``correct`` with exit
code 3 and the family's three exact checks; the fp8 control and each
planted fault read above the rehearsal's limits where a sound run reads
rounding; the generator keeps the promise the pad plan rests on."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import cell as cellmod
import compare
import families
import firststeps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "sdar-30b-a3b-chat.train-blockdiff-docs"


def test_every_group_is_the_same_lengths_in_another_order():
    cell = cellmod.load_cell(CELL)
    fam = families.load("token_documents")
    lengths = sorted(cell.traffic["lengths"])
    assert sum(lengths) == 8192 and len(lengths) == cell.batch_size == 16
    orders = []
    for seed in (1, 3_000_000_123):
        raw = fam.generate(cell.traffic, seed)
        assert len(raw) == 160
        for g in range(10):
            group = [len(r["tokens"]) for r in raw[16 * g:16 * (g + 1)]]
            assert sorted(group) == lengths
            orders.append(tuple(group))
        ids = np.concatenate([r["tokens"] for r in raw])
        assert ids.min() >= 0 and ids.max() < cell.traffic["vocab"] - 1  # the last row is the mask id
        rates = np.concatenate([r["rate"] for r in raw])
        assert rates.min() >= 0.1 and rates.max() <= 1.0
        again = fam.generate(cell.traffic, seed)
        assert all(np.array_equal(a["tokens"], b["tokens"]) and np.array_equal(a["masked"], b["masked"])
                   for a, b in zip(raw, again))
    assert len(set(orders)) > 10
    sample = fam.program_samples(raw[:1])[0]
    n = len(raw[0]["tokens"])
    assert sample.x.dtype == np.int32 and sample.x.shape == (2 * n, 3) and sample.num_edges == 0
    assert int((sample.node_targets["token_weight"] > 0).sum()) == int(raw[0]["masked"].sum())


def test_configuration_file_keeps_the_published_widths():
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == "sdar-30b-a3b-chat"][0]
    published = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
                 "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
                 "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
                 "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
                 "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
                 "use_sliding_window": False, "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if cfg.get(k, "absent") != v)
    assert differs == sorted(entry["reduced"]) == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    arch = cfg["run_training"]["NeuralNetwork"]["Architecture"]
    assert (arch["hidden_dim"], arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]) == (2048, 32, 4, 128)
    assert (arch["num_experts"], arch["num_experts_per_tok"], arch["moe_intermediate_size"]) == (128, 8, 768)
    assert (arch["experts_held"], arch["num_conv_layers"], arch["vocab_size"]) == (16, 4, 18992)
    assert cfg["deployment"]["chips_per_layer"] == 8 and entry["source"] == cfg["source"]
    for key in ("block_length", "schedule", "clip", "auxiliary_loss", "optimizer"):
        assert key in cfg["assumed"]


def test_rehearsal_is_correct_and_exits_3():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "3100000011",
         "--seconds", "1", "--rehearse"], env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0, result["checks"]
    assert list(result["checks"]) == ["loss_gap", "grad_gap", "update_gap", "documents_step_diff", "rows_step_diff",
                                      "masked_rows_step_diff"]
    assert result["run"]["dispatch_mode"] == "scan_epoch" and result["run"]["compiles_in_window"] == 0
    real = result["run"]["compare"]["real"]
    assert real["rows_per_epoch"] == 2 * real["tokens_per_epoch"] == 480
    assert real["held_assignments_per_epoch"] > 0 and 0.0 <= real["routing_flipped_rows_share"] < 0.5
    assert sorted(result["rehearsal_metrics"]) == ["setup_s", "train_graphs_per_s"] and result["metrics"] == {}


@pytest.fixture(scope="module")
def captured():
    cell = cellmod.load_cell(CELL, rehearse=True)
    taps, raw = firststeps.capture(cell, 3_100_000_012, os.path.join(HERE, "_work", "token_documents"))
    return cell, taps, raw, cell.fam.reference_run(cell, taps, raw)


def test_sound_first_steps_read_rounding(captured):
    cell, taps, raw, ref = captured
    nums = compare.numbers(compare.program_side(taps), ref, taps.initial_params)
    for name in compare.NUMBERS:
        assert nums[name] <= cell.limits[name] / 10, (name, nums[name])
    assert all(c["value"] == 0 for c in cell.fam.exact_checks(taps, ref).values())


@pytest.mark.parametrize("who,kw", [("control_fp8", {"quant": "fp8"}), ("half_batch", {"fault": "half_batch"}),
                                    ("causal_mask", {"fault": "causal_mask"}), ("lost_expert", {"fault": "lost_expert"})])
def test_control_and_faults_read_above_the_limits(captured, who, kw):
    cell, taps, raw, ref = captured
    assert who == "control_fp8" or who in cell.fam.faults(cell)
    side = cell.fam.reference_run(cell, taps, raw, **kw)
    nums = compare.numbers(side, ref, taps.initial_params)
    over = [name for name in compare.NUMBERS if nums[name] > cell.limits[name]]
    if who == "half_batch":  # the count of documents a step sees it too, exactly
        assert side["graphs"] == [d // 2 for d in ref["graphs"]]
    assert over, {name: nums[name] for name in compare.NUMBERS}


def test_control_readings_without_a_run_are_the_captured_ones(captured):
    """``families/token_documents.py --workload ... --seeds ...``: the upper
    readings from the program's loader and model alone are the numbers that
    a control gives against a captured run's reference."""
    cell, taps, raw, ref = captured
    lines = list(cell.fam.control_readings(cell, taps.seed, ["control_fp8", "fault_lost_expert"]))
    assert [ln["who"] for ln in lines] == ["reference", "control_fp8", "fault_lost_expert"]
    assert lines[0]["losses"] == ref["losses"]
    for ln, kw in zip(lines[1:], ({"quant": "fp8"}, {"fault": "lost_expert"})):
        nums = compare.numbers(cell.fam.reference_run(cell, taps, raw, **kw), ref, taps.initial_params)
        for name in compare.NUMBERS + compare.OPTIONAL:
            assert ln[name] == nums[name], (ln["who"], name)
