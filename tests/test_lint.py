"""Config lint (--check) tests: unknown keys flagged with suggestions,
free-form tables accepted, in-repo configs clean."""

import os
import subprocess
import sys

from flowgger_tpu.config import Config
from flowgger_tpu.lint import lint_config

REPO = os.path.join(os.path.dirname(__file__), "..")


def test_known_keys_clean():
    cfg = Config.from_string(
        '[input]\ntype = "stdin"\nformat = "rfc5424_tpu"\n'
        "tpu_batch_size = 1024\n"
        '[output]\ntype = "file"\nfile_path = "/tmp/x"\n')
    assert lint_config(cfg) == []


def test_typo_suggestion():
    cfg = Config.from_string('[input]\nfromat = "rfc5424"\n')
    warns = lint_config(cfg)
    assert len(warns) == 1
    assert "input.fromat" in warns[0]
    assert "input.format" in warns[0]


def test_free_tables_accepted():
    cfg = Config.from_string(
        "[input.ltsv_schema]\ncounter = \"u64\"\n"
        "[output.gelf_extra]\nanything_here = \"v\"\n")
    assert lint_config(cfg) == []


def test_repo_configs_are_clean():
    for rel in ("flowgger.toml", os.path.join("examples", "multihost-dp.toml")):
        cfg = Config.from_path(os.path.join(REPO, rel))
        assert lint_config(cfg) == [], rel


def test_cli_check_flag():
    r = subprocess.run(
        [sys.executable, "-m", "flowgger_tpu", "--check", "flowgger.toml"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_cli_check_flag_bad(tmp_path):
    bad = tmp_path / "bad.toml"
    bad.write_text('[output]\nkafka_compresion = "gzip"\n')
    r = subprocess.run(
        [sys.executable, "-m", "flowgger_tpu", "--check", str(bad)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "kafka_compression" in r.stdout  # the suggestion


def test_cli_check_exit_codes(tmp_path):
    """Exit-code contract: 0 clean / 1 unknown keys / 2 unreadable or
    invalid TOML — distinct, so deploy gates can tell them apart."""
    unknown = tmp_path / "unknown.toml"
    unknown.write_text('[input]\nnot_a_real_key = 1\n')
    r = subprocess.run(
        [sys.executable, "-m", "flowgger_tpu", "--check", str(unknown)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "not_a_real_key" in r.stdout

    r = subprocess.run(
        [sys.executable, "-m", "flowgger_tpu", "--check",
         str(tmp_path / "missing.toml")],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 2
    assert "error:" in r.stderr

    invalid = tmp_path / "invalid.toml"
    invalid.write_text("this is [not toml\n")
    r = subprocess.run(
        [sys.executable, "-m", "flowgger_tpu", "--check", str(invalid)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 2
    assert "error:" in r.stderr


def test_namespace_is_derived_from_code():
    """lint.py no longer hand-maintains KNOWN_KEYS: the namespace comes
    from the lookup call sites, so the four drifted keys the old set
    carried are gone and every key the code reads is present."""
    from flowgger_tpu.lint import FREE_TABLES, KNOWN_KEYS

    for dead in ("metrics.jsonl", "input.tls_threads",
                 "output.tls_compatibility_level", "output.tls_compression",
                 "input.tpu_pallas"):
        assert dead not in KNOWN_KEYS, dead
    assert len(KNOWN_KEYS) == 153
    for live in ("input.format", "input.tpu_batch_size",
                 "input.tpu_breaker_fallback_ratio", "input.queue_policy",
                 "output.kafka_retry_init", "output.tls_recovery_delay_max",
                 "supervisor.max_restarts", "metrics.jax_profile_dir"):
        assert live in KNOWN_KEYS, live
    assert {"faults", "input.ltsv_schema", "output.gelf_extra"} <= FREE_TABLES


def test_dead_key_now_warns():
    """A key the old hand-written set wrongly accepted is flagged."""
    cfg = Config.from_string("[metrics]\njsonl = true\n")
    warns = lint_config(cfg)
    assert len(warns) == 1 and "metrics.jsonl" in warns[0]
