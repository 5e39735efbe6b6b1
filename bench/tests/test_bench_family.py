"""Model families: a configuration names its family, the loader refuses
one that names none or an unknown one, and a configuration of a second
family runs from new files alone, with no file of the benchmark edited."""
import json

import pytest

import _setup
import run as bench

FAMILY = "emsnet_bert_gru"
TINY_LIMIT = {"output_gap": 1e-4, "mean_gap": 1e-5}
# a second family: the first one's code under another name, as a new
# architecture's module would sit beside it
ALIAS = '''"""emsnet_bert_gru under another name."""
from families.emsnet_bert_gru import (  # noqa: F401
    encoder_flops, head_flops, kernel_work, make_params, program_config,
    reference_outputs)
'''


def benchmark_with(tmp_path, family):
    """A copy of ``BENCHMARK.json`` in ``tmp_path`` with one more
    configuration (the tinybert file with its ``family`` replaced, or
    left out where ``family`` is None) and one cell of it."""
    spec = json.loads(bench.SPEC.read_text())
    tiny = next(c for c in spec["configs"]
                if c["name"] == "emsnet-tinybert-gru")
    model_file = json.loads((bench.ROOT / tiny["file"]).read_text())
    model_file.pop("family")
    if family is not None:
        model_file["family"] = family
    model_file["name"] = "emsnet-second"
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "emsnet-second.json").write_text(
        json.dumps(model_file))
    spec["configs"].append(dict(tiny, name="emsnet-second",
                                file="configs/emsnet-second.json"))
    spec["workloads"].append({
        "name": "second.steady", "config": "emsnet-second",
        "traffic": "steady.tinybert", "chips": 1,
        "why": "a configuration of a second family"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


@pytest.mark.parametrize("family", [None, "emsnet_bert_lstm"],
                         ids=["no_key", "unknown"])
def test_configuration_without_a_known_family_is_refused(tmp_path, family):
    spec_path = benchmark_with(tmp_path, family)
    with pytest.raises(ValueError, match=f"families found: .*'{FAMILY}'"):
        bench.load_cell("second.steady", spec_path)


def test_a_second_family_runs_from_new_files_alone(tmp_path):
    families = tmp_path / "families"
    families.mkdir()
    (families / "emsnet_alias.py").write_text(ALIAS)
    spec_path = benchmark_with(tmp_path, "emsnet_alias")
    seen = {}

    def on_check(family, params, model, rows, ref, got):
        seen["family"] = family.__name__

    res = bench.run("second.steady", 2**35 + 29, 2.0, False,
                    chips_check=False, model_override=_setup.TINY_MODEL,
                    serving_override=_setup.TINY_SERVING,
                    traffic_override=_setup.TINY_TRAFFIC, limits=TINY_LIMIT,
                    spec_path=spec_path,
                    family_dirs=(families, bench.FAMILIES),
                    on_check=on_check)
    assert seen["family"] == "bench_family_emsnet_alias"
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
