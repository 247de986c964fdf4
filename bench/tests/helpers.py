"""Run the benchmark in a child process on the CPU, as its rehearsal.

Each run is a process of its own, as on the chip: the child sets up JAX with
as many CPU devices as the cell asks for chips, and its compile cache goes
to the test's temporary directory."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cells whose files are all in bench/ but which BENCHMARK.json does not hold
# yet; their rehearsals keep those files working until a change adds them
CANDIDATES = [
    {"name": "web3hop-x4.hotspot", "config": "grouting-web-3hop-x4", "traffic": "hotspot",
     "chips": 4, "why": "3-hop hotspot queries over storage sharded on 4 chips"},
]


def candidates_root(tmp_path) -> str:
    """A root whose BENCHMARK.json holds the candidate cells beside the
    benchmark's own, over the repo's `bench/`."""
    root = tmp_path / "root"
    root.mkdir()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] += CANDIDATES
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench").symlink_to(os.path.join(REPO, "bench"))
    return str(root)


def run_child(code: str, tmp_path, chips: int = 1, timeout: int = 600):
    """(exit code, stdout lines, stderr) of `code` run in a child process
    from the repo's root, with `bench` and the program importable."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env, text=True,
                       capture_output=True, timeout=timeout)
    return p.returncode, p.stdout.splitlines(), p.stderr


def rehearse(cell: str, tmp_path, chips: int = 1, seed: int = 2**31 + 7,
             seconds: float = 1.0, trace: int = 0, prelude: str = "",
             chain_cap=None, extra=(), root=None):
    """One rehearsal run of `cell`, under `root` (default: the repo); returns
    (exit code, result or None, stderr)."""
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace), "--rehearse", *extra]
    code = (f"import sys\n{prelude}\nfrom bench import run\n"
            f"sys.exit(run.main({argv!r}, root={root!r}, chain_cap={chain_cap!r}))\n")
    rc, out, err = run_child(code, tmp_path, chips)
    result = json.loads(out[-1]) if out and out[-1].startswith("{") else None
    return rc, result, err
