"""Tier-1 hook for the benchmark's own checks.

``chipbench/selftest/`` holds the yardstick's tests (the manifest, the
training loop at ``BERT_TINY`` width and at a small decoder's, the trace
reductions and the readers on the trimmed recordings, the FLOP functions): a benchmark PR may add no file outside its
directories, so they live there, and this file imports them so that each
counts in the driver's run on the CPU.  Nothing here measures anything.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.selftest.test_chipbench import *  # noqa: E402,F401,F403
from chipbench.selftest.test_program_spans import *  # noqa: E402,F401,F403
from chipbench.selftest.test_kanana import *  # noqa: E402,F401,F403
from chipbench.selftest.test_mellum2 import *  # noqa: E402,F401,F403
from chipbench.selftest.test_granite import *  # noqa: E402,F401,F403
from chipbench.selftest.test_nemotron import *  # noqa: E402,F401,F403

from chipbench import manifest as _mf  # noqa: E402
from chipbench.selftest import test_mellum2 as _mellum2  # noqa: E402

# Two of ``selftest/test_mellum2.py``'s checks find mellum2's entries at
# the manifest's end, where PR 38's granite entries now lie: under
# ``pytest chipbench/selftest`` they fail, and a benchmark PR is to make
# them find the entries by name (PERF.md, section 7).  Until then these
# take their names here and make the same checks on the real manifest,
# each entry found by its name.


def _named(manifest, group, name):
    (entry,) = [e for e in manifest[group] if e["name"] == name]
    return entry


def test_mellum2_entries_validate_and_the_cell_reports_every_metric():
    manifest = _mf.validate(_mf.load_manifest())
    cell = _mf.load_cell(_mellum2.MELLUM2_CELL, manifest)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["require_kernels"] == [
        "flash_attention_fwd", "flash_attention_dkv", "flash_attention_dq",
        "ragged-dot"]
    assert cell["traffic"] == dict(
        cell["traffic"], driver="train_loop", seq_len=8192, batch=2, ring=8,
        offsets=32, warmup_steps=4, trace_steps=8, zipf_exponent=1.1)
    reported = _mf.metrics_of(manifest, "per_layer", _mellum2.MELLUM2_CELL)
    unlisted = [m["name"] for m in manifest["per_layer"]
                if "workloads" not in m]
    assert set(unlisted) | set(_mellum2.MELLUM2_METRICS) == set(reported)
    assert "dispatch_lead_ms" in reported
    # appended together, in the issue's order, each for this cell alone
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(_mellum2.MELLUM2_METRICS[0])
    assert names[first:first + len(_mellum2.MELLUM2_METRICS)] == list(
        _mellum2.MELLUM2_METRICS)
    for name in _mellum2.MELLUM2_METRICS:
        m = _named(manifest, "per_layer", name)
        assert m["workloads"] == [_mellum2.MELLUM2_CELL]
        assert m["moves"] == "train_examples_per_s"
        assert callable(_mf.load_by_name("layer_metrics", name).read)
    assert _named(manifest, "workloads", _mellum2.MELLUM2_CELL)[
        "config"] == "mellum2_12b_a2_5b"
    assert set(_mf.metrics_of(manifest, "end_to_end",
                              _mellum2.MELLUM2_CELL)) == {
        "train_examples_per_s", "step_ms_p95", "setup_s"}


def test_mellum2_configuration_is_the_catalog_row_but_for_what_it_lists():
    published, period = _mellum2.MELLUM2_PUBLISHED, _mellum2.PERIOD
    config = _mf.load_cell(_mellum2.MELLUM2_CELL)["config"]
    entry = _named(_mf.load_manifest(), "configs", "mellum2_12b_a2_5b")
    assert config["source"] == entry["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
        "main/config.json")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"])
    assert config["published"] == {k: published[k] for k in differs}
    share = config["deployment"]
    assert share["chips_sharing_a_layer"] * config["num_experts"] == \
        share["num_experts_routed_over"] == published["num_experts"]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    # the guide's floors: whole periods and four layers, 8 experts, 1/8
    assert config["num_hidden_layers"] % 4 == 0 \
        and config["num_hidden_layers"] >= 4
    assert config["num_experts"] >= 8 and len(config["assumed"]) >= 8
    # what the builder hands models/decoder.py: the published widths
    built = _mf.load_by_name("builders", "mellum2").model_config(config, True)
    assert built["layer_types"] == period * (config["num_hidden_layers"] // 4)
    assert (built["n_routed_experts"], built["experts_held"],
            built["num_experts_per_tok"], built["n_shared_experts"],
            built["first_k_dense_replace"]) == (64, 8, 8, 0, 0)
    assert (built["hidden_size"], built["num_attention_heads"],
            built["num_key_value_heads"], built["head_dim"],
            built["moe_intermediate_size"], built["sliding_window"]) == (
        2304, 32, 4, 128, 896, 1024)
    assert built["rope_parameters"] == published["rope_parameters"]
    assert built["scoring_func"] == "softmax" and built["attention"] == "gqa"
