"""The bring-up rules (CPU side): one process per chip, no silent CPU on a
path that claims the device, a compile cache that can be placed from
outside, and the `chip_smoke.py` contract.

The chip itself is proved by `python chip_smoke.py` through the chip tool;
here the same script must REFUSE a CPU backend by default and pass only as
an explicit, marked rehearsal.
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, timeout=300, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    e.pop("XLA_FLAGS", None)  # the conftest's 8 virtual devices stay here
    return subprocess.run(
        [sys.executable, *argv], cwd=ROOT, env=e, capture_output=True,
        text=True, timeout=timeout,
    )


# -- compile cache placement --------------------------------------------------


class TestCompileCache:
    def _recorded(self, monkeypatch):
        import jax

        calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.append((k, v))
        )
        return calls

    def test_env_set_means_code_sets_nothing(self, monkeypatch):
        from emqx_tpu import compile_cache

        calls = self._recorded(monkeypatch)
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/placed")
        assert compile_cache.place_compile_cache() == "/somewhere/placed"
        assert calls == []

    def test_unset_means_the_fixed_in_checkout_path(self, monkeypatch):
        from emqx_tpu import compile_cache

        calls = self._recorded(monkeypatch)
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.place_compile_cache() == compile_cache.DEFAULT_DIR
        assert calls == [
            ("jax_compilation_cache_dir", compile_cache.DEFAULT_DIR)
        ]
        assert compile_cache.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")

    def test_path_is_the_same_in_every_process(self):
        """Never built from a pid, the time or tempfile: the directory is
        part of the cache key, so a path that moves would never hit."""
        from emqx_tpu import compile_cache

        code = "from emqx_tpu.compile_cache import DEFAULT_DIR as D; print(D)"
        seen = {_run(["-c", code]).stdout.strip() for _ in range(2)}
        assert seen == {compile_cache.DEFAULT_DIR}
        assert not compile_cache.DEFAULT_DIR.startswith(tempfile.gettempdir())

    def test_no_other_code_places_the_cache(self):
        sources = [os.path.join(ROOT, f) for f in (
            "chip_smoke.py", "__graft_entry__.py")]
        for top in ("emqx_tpu", "tools"):
            for d, _dirs, files in os.walk(os.path.join(ROOT, top)):
                sources += [
                    os.path.join(d, f) for f in files if f.endswith(".py")
                ]
        placing = [
            os.path.relpath(p, ROOT) for p in sources
            if "jax_compilation_cache_dir" in open(p).read()
        ]
        assert placing == ["emqx_tpu/compile_cache.py"]


# -- the device is named, never assumed ---------------------------------------


class TestNoSilentCpu:
    def test_only_tpu_is_a_platform_of_record(self):
        from emqx_tpu.observe import provenance

        assert provenance._RECORD_PLATFORMS == ("tpu",)

    def test_device_peaks(self, monkeypatch):
        from emqx_tpu.observe import profiler, provenance

        fp = provenance.fingerprint()
        assert profiler.device_peaks() is None  # CPU: nothing to render

        def on(kind):
            monkeypatch.setattr(provenance, "_CACHE", dict(
                fp, platform="tpu", device_kind=kind, proxy=False))

        on("TPU v5 lite")  # how the v5e names itself
        assert profiler.device_peaks()["peak_bytes_per_s"] == 819e9
        on("TPU v9 imaginary")
        with pytest.raises(LookupError, match="TPU v9 imaginary"):
            profiler.device_peaks()

    def test_worker_and_client_imports_stay_off_jax(self):
        """Connection workers and the smoke's driver run beside the one
        process that holds the chip: their import chains must not load
        jax at all."""
        code = (
            "import sys; import emqx_tpu.transport.workers, chip_smoke; "
            "from emqx_tpu.app import build_guard_hooks; "
            "from emqx_tpu.transport.connection import Connection; "
            "assert 'jax' not in sys.modules"
        )
        r = _run(["-c", code])
        assert r.returncode == 0, r.stderr[-2000:]


# -- chip_smoke.py --------------------------------------------------------------


class TestChipSmoke:
    def test_default_run_refuses_a_cpu_backend(self):
        r = _run(["chip_smoke.py"])
        assert r.returncode != 0
        assert "found platform 'cpu'" in r.stderr
        assert '"ok"' not in r.stdout

    def test_explicit_rehearsal_passes_and_says_it_is_not_a_chip_run(self):
        r = _run(["chip_smoke.py", "--rehearse-cpu"], timeout=600)
        assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
        assert "CPU REHEARSAL (not a chip run)" in r.stdout
        last = json.loads(r.stdout.strip().splitlines()[-1])
        assert last == {
            "ok": True, "chip_run": False,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        }
