import json
import re

import pytest

from bench import manifest, peaks

MAN = manifest.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_every_cell_finds_its_files_by_name(cell):
    c = manifest.cell(MAN, cell)
    cfg = manifest.config(MAN, c["config"])
    assert cfg["name"] == c["config"]
    assert manifest.traffic(c["traffic"])["kind"]
    lim = manifest.limits(cell)
    assert lim["max_logit_gap"] > 0 and lim["min_tokens"] > 0
    e2e = {m["name"] for m in manifest.end_to_end(MAN, c)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = manifest.per_layer(MAN, c)
    assert layer
    for m in layer:
        assert callable(manifest.metric_reader(m["name"]))
        assert m["moves"] in e2e


def test_names_units_and_keys_keep_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MAN[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    assert len(json.dumps(MAN)) < 64 * 1024


def test_config_files_list_every_changed_key():
    for c in MAN["configs"]:
        cfg = manifest.config(MAN, c["name"])
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg.get("published", {})) == set(c["reduced"])


def test_unknown_device_kind_is_refused():
    assert peaks.peak_for("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("TPU v9 imaginary")


def test_missing_files_are_refused(tmp_path):
    with pytest.raises(manifest.ManifestError):
        manifest.traffic("no-such-mix")
    with pytest.raises(manifest.ManifestError):
        manifest.metric_reader("no_such_metric")
    with pytest.raises(manifest.ManifestError):
        manifest.cell(MAN, "no-such-cell")
    with pytest.raises(manifest.ManifestError):
        manifest.load(tmp_path)
