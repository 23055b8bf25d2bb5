"""The ``causal_documents`` family and its cell on the CPU: the rehearsal of
``joyai-llm-flash.train-causal-mtp-docs`` comes out ``correct`` with exit
code 3 and the family's three exact checks; the fp8 control and each
planted fault read above the rehearsal's limits where a sound run reads
rounding; the generator keeps the promise the pad plan rests on; the
configuration file holds the catalog's numbers but those it names in
``reduced``."""

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
CELL = "joyai-llm-flash.train-causal-mtp-docs"


def test_every_group_is_the_same_lengths_in_another_order():
    cell = cellmod.load_cell(CELL)
    fam = families.load("causal_documents")
    lengths = sorted(cell.traffic["lengths"])
    assert sum(lengths) == 8192 and len(lengths) == cell.batch_size == 16
    orders = []
    for seed in (1, 3_300_000_123):
        raw = fam.generate(cell.traffic, seed)
        assert len(raw) == 160
        for g in range(10):
            group = [len(r["tokens"]) for r in raw[16 * g:16 * (g + 1)]]
            assert sorted(group) == lengths
            orders.append(tuple(group))
        ids = np.concatenate([r["tokens"] for r in raw])
        assert ids.min() >= 0 and ids.max() < cell.traffic["vocab"]
        again = fam.generate(cell.traffic, seed)
        assert all(np.array_equal(a["tokens"], b["tokens"]) for a, b in zip(raw, again))
    assert len(set(orders)) > 10
    sample = fam.program_samples(raw[:1])[0]
    n = len(raw[0]["tokens"])
    assert sample.x.dtype == np.int32 and sample.x.shape == (n, 3) and sample.num_edges == 0
    assert int(sample.node_targets["token_weight"].sum()) == n - 1 and int(sample.node_targets["token_mtp_weight"].sum()) == n - 2
    assert np.array_equal(sample.node_targets["token_mtp"][: n - 2, 0], raw[0]["tokens"][2:])


def test_configuration_file_keeps_the_catalogs_numbers():
    with open(os.path.join(BENCH, "configs", "joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == "joyai-llm-flash"][0]
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
    }
    differs = sorted(k for k, v in catalog.items() if cfg.get(k, "absent") != v)
    assert differs == sorted(entry["reduced"]) == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    arch = cfg["run_training"]["NeuralNetwork"]["Architecture"]
    assert (arch["hidden_dim"], arch["num_attention_heads"], arch["q_lora_rank"], arch["kv_lora_rank"]) == (2048, 32, 1536, 512)
    assert (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"], arch["v_head_dim"]) == (128, 64, 128)
    assert (arch["num_experts"], arch["num_experts_per_tok"], arch["moe_intermediate_size"], arch["intermediate_size"]) == (256, 8, 768, 7168)
    assert (arch["experts_held"], arch["num_conv_layers"], arch["vocab_size"], arch["first_k_dense_replace"]) == (8, 5, 16160, 1)
    assert cfg["deployment"]["chips_per_layer"] == 32 and entry["source"] == cfg["source"]
    for key in ("bias_update_speed", "mtp_weight", "auxiliary_loss", "optimizer", "precision"):
        assert key in cfg["assumed"]


def test_rehearsal_is_correct_and_exits_3():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "3300000011",
         "--seconds", "1", "--rehearse"], env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0, result["checks"]
    assert list(result["checks"]) == ["loss_gap", "grad_gap", "update_gap", "documents_step_diff", "rows_step_diff",
                                      "mtp_rows_step_diff"]
    assert result["run"]["dispatch_mode"] == "scan_epoch" and result["run"]["compiles_in_window"] == 0
    real = result["run"]["compare"]["real"]
    assert real["rows_per_epoch"] == real["tokens_per_epoch"] == 240
    assert real["mtp_rows_per_epoch"] == 240 - 2 * 16 and real["held_assignments_per_epoch"] > 0
    assert sorted(result["rehearsal_metrics"]) == ["setup_s", "train_graphs_per_s"] and result["metrics"] == {}


@pytest.fixture(scope="module")
def captured():
    cell = cellmod.load_cell(CELL, rehearse=True)
    taps, raw = firststeps.capture(cell, 3_300_000_012, os.path.join(HERE, "_work", "causal_documents"))
    return cell, taps, raw, cell.fam.reference_run(cell, taps, raw)


def test_sound_first_steps_read_rounding(captured):
    cell, taps, raw, ref = captured
    nums = compare.numbers(compare.program_side(taps), ref, taps.initial_params)
    for name in compare.NUMBERS:
        assert nums[name] <= cell.limits[name] / 10, (name, nums[name])
    assert all(c["value"] == 0 for c in cell.fam.exact_checks(taps, ref).values())


@pytest.mark.parametrize("who,kw", [("control_fp8", {"quant": "fp8"}), ("half_batch", {"fault": "half_batch"}),
                                    ("mtp_next", {"fault": "mtp_next"}), ("softmax_router", {"fault": "softmax_router"}),
                                    ("full_rope", {"fault": "full_rope"})])
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
    """``families/causal_documents.py --workload ... --seeds ...``: the upper
    readings from the program's loader and model alone are the numbers that
    a control gives against a captured run's reference."""
    cell, taps, raw, ref = captured
    lines = list(cell.fam.control_readings(cell, taps.seed, ["control_fp8", "fault_mtp_next"]))
    assert [ln["who"] for ln in lines] == ["reference", "control_fp8", "fault_mtp_next"]
    assert lines[0]["losses"] == ref["losses"]
    for ln, kw in zip(lines[1:], ({"quant": "fp8"}, {"fault": "mtp_next"})):
        nums = compare.numbers(cell.fam.reference_run(cell, taps, raw, **kw), ref, taps.initial_params)
        for name in compare.NUMBERS + compare.OPTIONAL:
            assert ln[name] == nums[name], (ln["who"], name)
