"""Every traffic file loads, and a file the generator cannot write is
refused."""

import json
import os

import pytest

from benchmark import traffic

MIXES = sorted(os.path.splitext(f)[0] for f in os.listdir(
    os.path.join(os.path.dirname(traffic.__file__), "traffic")))


@pytest.mark.parametrize("name", MIXES)
def test_every_traffic_file_loads(name):
    mix = traffic.load(name)
    assert mix["judged"] == "tput" and mix["rate_lines_per_s"] == "max"
    assert mix["pool_lines"] >= 8192 and mix["warm_min_s"] >= 0
    assert 1 <= mix["sources"] <= traffic.MAX_SOURCES


def test_the_number_of_sources():
    assert traffic.load("drain")["sources"] == 1      # the default: one pipe
    assert traffic.load("fleet_catchup")["sources"] == 64


def test_stamps_of_one_write_stay_within_a_millisecond():
    s = traffic.stamps(1_000_000, 2500)
    assert s.min() == 1_000_000 and s.max() == 1_000_999
    assert s[1000] == s[0]


@pytest.mark.parametrize("bad", [
    {"judged": "tput", "corpus": "loghub_syslog", "rate_lines_per_s": 0},
    {"judged": "tput", "corpus": "loghub_syslog", "rate_lines_per_s": 8000},
    {"judged": "tput", "corpus": "no_such", "rate_lines_per_s": "max"},
    {"corpus": "loghub_syslog", "rate_lines_per_s": "max"},
    {"judged": "tput", "corpus": "loghub_syslog", "rate_lines_per_s": "max",
     "sources": 0},
    {"judged": "tput", "corpus": "loghub_syslog", "rate_lines_per_s": "max",
     "sources": 1025},
    {"judged": "tput", "corpus": "loghub_syslog", "rate_lines_per_s": "max",
     "sources": "64"},
    {"judged": "tput", "corpus": "loghub_syslog", "rate_lines_per_s": "max",
     "sources": 2.5},
    {"judged": "tput", "corpus": "loghub_syslog", "rate_lines_per_s": "max",
     "sources": True},
])
def test_a_file_the_generator_cannot_write_is_refused(tmp_path, monkeypatch,
                                                      bad):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "bad.json").write_text(json.dumps(bad))
    os.symlink(os.path.join(traffic.HERE, "corpora"), tmp_path / "corpora")
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError):
        traffic.load("bad")
