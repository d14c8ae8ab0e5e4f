"""The start-up rules: a CPU run and a chip run cannot be confused.

``chip_smoke.py`` is the proof that the system starts on the chip; here,
on the CPU, it must FAIL — with every phase walked when it is asked to
rehearse. The tools run their CPU smoke configs only under the explicit
CPU request and exit non-zero when nothing is pinned and no chip comes
up. The compile cache sits where ``JAX_COMPILATION_CACHE_DIR`` says,
else at one fixed path inside the checkout.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BARE = {"PATH": os.environ.get("PATH", ""),
        "HOME": os.environ.get("HOME", "/root")}
CPU = dict(BARE, JAX_PLATFORMS="cpu")


def _smoke(*args, env=CPU, timeout=600):
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        *args], capture_output=True, text=True,
                       timeout=timeout, env=env, cwd="/")
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, r.stderr[-2000:]
    return r, lines


def test_explicit_cpu_request_fails_and_names_the_cpu():
    r, lines = _smoke()
    assert r.returncode != 0
    assert r.stdout.splitlines()[-1].startswith('{"ok": false')
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    # no chip is a failure, never a smaller run: nothing ran past the
    # device phase
    assert [ln["phase"] for ln in lines[:-1]] == ["device"]


class TestRehearsal:
    """``--rehearse`` on the CPU walks every phase at tiny size — one
    run, one case per phase, so a broken phase is named."""

    @pytest.fixture(scope="class")
    def run(self):
        r, lines = _smoke("--rehearse")
        return r, lines, {ln["phase"]: ln for ln in lines[:-1]}

    def test_is_not_a_result(self, run):
        r, lines, phases = run
        assert list(phases) == ["device", "kernels", "train_lm",
                                "train_rn50", "serve"]
        assert not phases["device"]["ok"]           # still not a chip
        assert lines[-1] == {"ok": False, "device": {
            "platform": "cpu", "kind": "cpu", "count": 1}}
        assert r.returncode == 1

    @pytest.mark.parametrize("name", ["kernels", "train_lm", "train_rn50",
                                      "serve"])
    def test_phase(self, run, name):
        r, _, phases = run
        ln = phases[name]
        assert ln["ok"], (ln.get("error"), r.stderr[-2000:])
        assert ln["seconds"] > 0 and "compile_seconds" in ln
        if name == "kernels":
            assert len(ln["checked"]) >= 12
        elif name == "train_lm":
            assert ln["losses"][-1] < ln["losses"][0]
            assert abs(ln["losses"][0] - ln["reference_loss"]) \
                <= ln["tolerance"]
        elif name == "train_rn50":
            assert ln["scaler"]["step_count"] == 3
            assert ln["scaler"]["overflow_count"] == 0
        else:
            assert ln["completed"] == ln["config"]["requests"] >= 16
            assert ln["prefix_hits"] >= 1
            assert ln["compilations_after_warmup"] == 0
            assert ln["streams_equal"] == ln["compared"] >= 2


class TestChips4Rehearsal:
    """``--chips 4`` on four virtual CPU devices: the multi-chip phase
    and what it is compared with, and no other phase."""

    @pytest.fixture(scope="class")
    def run(self):
        env = dict(CPU,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        return _smoke("--chips", "4", "--rehearse", env=env)

    def test_runs_only_the_multichip_phase(self, run):
        r, lines = run
        assert [ln["phase"] for ln in lines[:-1]] == ["device", "multichip"]
        assert lines[1]["ok"], (lines[1].get("error"), r.stderr[-2000:])
        assert lines[-1] == {"ok": False, "device": {
            "platform": "cpu", "kind": "cpu", "count": 4}}
        assert r.returncode == 1

    def test_zero_state_is_a_quarter_on_each_of_four_devices(self, run):
        arms = run[1][1]["arms"]
        assert list(arms) == ["one_device", "ddp", "zero"]
        assert arms["zero"]["shard_devices"] == [0, 1, 2, 3]
        assert len(set(arms["zero"]["shard_bytes"])) == 1
        assert "all-reduce" in arms["ddp"]["collectives"]
        assert {"reduce-scatter", "all-gather"} <= set(
            arms["zero"]["collectives"])

    def test_the_three_arms_agree(self, run):
        multi = run[1][1]
        first = [a["losses"][0] for a in multi["arms"].values()]
        assert max(first) - min(first) <= 0.02
        assert multi["ddp_vs_zero"]["update_rel_l2"] <= 0.02


class TestNoChipIsAFailure:
    """With nothing pinned and no chip, jax comes up on the CPU with a
    warning. Every measurement entry point must turn that into a
    non-zero exit — no CPU smoke config, no result line."""

    @pytest.mark.parametrize("tool", ["tools/serve_bench.py",
                                      "tools/decode_bench.py"])
    def test_exits_nonzero_with_nothing_pinned(self, tool, tmp_path):
        r = subprocess.run([sys.executable, os.path.join(REPO, tool)],
                           capture_output=True, text=True, timeout=300,
                           env=BARE, cwd=tmp_path)
        assert r.returncode != 0, r.stdout[-500:]
        assert "no accelerator" in r.stderr
        for ln in r.stdout.splitlines():        # an error line, if any
            if ln.startswith("{"):
                assert json.loads(ln).get("value", 0.0) == 0.0


class TestCompileCache:
    @pytest.fixture
    def cache_config(self):
        """Put jax's cache settings back: this process must stay
        cache-less for the tests that compile for a described chip."""
        import jax
        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        was = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in was.items():
            jax.config.update(n, v)

    def test_honours_the_environment(self, monkeypatch, tmp_path,
                                     cache_config):
        import jax
        from apex_tpu.utils import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        # jax reads the variable itself; code set no other directory
        assert jax.config.jax_compilation_cache_dir == before
        # the 0.1-1 s kernel programs are kept too
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1

    def test_one_fixed_path_inside_the_checkout(self, tmp_path):
        """Same path from two working directories and two processes:
        the path is part of the cache key, so a directory that moved
        would never hit."""
        code = ("from apex_tpu.utils import enable_compile_cache\n"
                "import jax\n"
                "p = enable_compile_cache()\n"
                "assert jax.config.jax_compilation_cache_dir == p\n"
                "print(p)")
        other = tmp_path / "elsewhere"
        other.mkdir()
        procs = [subprocess.Popen(
            [sys.executable, "-c", code], stdout=subprocess.PIPE,
            text=True, cwd=cwd, env=dict(CPU, PYTHONPATH=REPO))
            for cwd in (tmp_path, other)]
        paths = [p.communicate(timeout=120)[0].strip() for p in procs]
        assert all(p.returncode == 0 for p in procs)
        assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")


def test_unknown_device_kind_has_no_peak():
    from apex_tpu.prof import PEAKS, chip_peak
    v5e = chip_peak("TPU v5 lite")
    assert (v5e.bf16_flops_per_s, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert all(p.source for p in PEAKS.values())
    with pytest.raises(ValueError, match="no published peak"):
        chip_peak("TPU v99")
    with pytest.raises(ValueError, match="no published peak"):
        chip_peak()            # the attached device is a CPU


def test_multiproc_children_never_claim_the_chip(tmp_path, monkeypatch):
    """One process per chip: local children are CPU simulations whatever
    the caller's environment says, so N of them cannot each claim the
    host's chips."""
    from apex_tpu.parallel import launch
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    script = tmp_path / "child.py"
    script.write_text(
        "import os, sys\n"
        "open(sys.argv[1] + os.environ['RANK'], 'w').write(\n"
        "    os.environ['JAX_PLATFORMS'])\n")
    assert launch.multiproc(str(script), 2, str(tmp_path / "plat"),
                            log_dir=str(tmp_path)) == 0
    assert [(tmp_path / f"plat{r}").read_text() for r in (0, 1)] \
        == ["cpu", "cpu"]


def test_native_library_name_follows_the_sources(tmp_path, monkeypatch):
    """A built ``.so`` is loaded only if it provably matches
    ``csrc/*.cpp``: its name carries their content hash, so a stale
    build in an ignored ``_build/`` is never found."""
    from apex_tpu.utils import native
    name = native._lib_name()
    assert name == native._lib_name()
    edited = tmp_path / "flat_runtime.cpp"
    with open(native._SRCS[0]) as f:
        edited.write_text(f.read() + "\n// edited\n")
    monkeypatch.setattr(native, "_SRCS", [str(edited)] + native._SRCS[1:])
    assert native._lib_name() != name
