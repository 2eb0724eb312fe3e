"""Static-analysis gate (in the default ``make test`` path via
``make analyze``): prove psanalyze is ALIVE, not just silent.

A linter that exits 0 forever is indistinguishable from one that
stopped looking. This smoke runs the suite both ways:

1. **clean tree** — ``python -m tools.psanalyze`` over the repo must
   exit 0 with zero findings;
2. **seeded defects** — for each of the six static rules, a temp copy
   of the tree gets exactly the defect class the rule exists for (an
   off-thread native call, a typo'd cfg key, a canonical metric key
   dropped from the schema, a codec claiming an algebra it doesn't
   implement, a shrunk PSF2 header, an undeclared telemetry sidecar
   prefix) and the rule must fire nonzero on it — plus one
   pragma-suppression check proving the allowlist works;
3. **sanitizer leg** — a deliberately out-of-bounds C snippet built
   with the ASan flags from ``utils/native.SANITIZE_FLAGS`` must be
   caught at runtime (the wiring ``make native-asan`` relies on
   detects a real bug, not just compiles).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: directories a seeded-defect tree needs (tools/ itself is the
#: analyzer, not an analysis target)
TREE_DIRS = ("pytorch_ps_mpi_tpu", "examples", "benchmarks", "docs",
             "native")

#: rule -> (file to mutate, old text, new text) — one seeded defect per
#: static rule, each the exact failure class the rule was built for
SEEDS = {
    "thread-affinity": (
        "pytorch_ps_mpi_tpu/serving/net.py",
        "            t0 = time.perf_counter()\n",
        "            t0 = time.perf_counter()\n"
        "            self.core.server._lib.tps_server_pump("
        "self.core.server._h)\n",
    ),
    "cfg-schema": (
        "pytorch_ps_mpi_tpu/parallel/async_train.py",
        'cfg.get("codec"',
        'cfg.get("codek"',
    ),
    "metrics-surface": (
        "pytorch_ps_mpi_tpu/telemetry/registry.py",
        '    "reads_shed",\n',
        "",
    ),
    "codec-contract": (
        "pytorch_ps_mpi_tpu/codecs/identity.py",
        "class IdentityCodec(Codec):",
        "class HollowCodec(Codec):\n"
        "    supports_aggregate = True\n"
        "\n"
        "\n"
        "class IdentityCodec(Codec):",
    ),
    "abi-drift": (
        "native/tcpps.cpp",
        "constexpr size_t kPsfHeader = 36;",
        "constexpr size_t kPsfHeader = 32;",
    ),
    # a new sidecar JSONL written under the telemetry dir WITHOUT a
    # SIDECAR_PREFIXES declaration — the exact "leaks into the
    # recorder-span merge" bug class the rule exists for
    "sidecar-registry": (
        "pytorch_ps_mpi_tpu/telemetry/lineage.py",
        'return os.path.join(lineage_dir, f"lineage-{name}.jsonl")',
        'return os.path.join(lineage_dir, f"sneaky-{name}.jsonl")',
    ),
}


def run_psanalyze(root: str, rules=None) -> "tuple[int, dict]":
    cmd = [sys.executable, "-m", "tools.psanalyze", "--json",
           "--root", root]
    if rules:
        cmd += ["--rules", ",".join(rules)]
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO,
                       timeout=300)
    try:
        doc = json.loads(p.stdout)
    except json.JSONDecodeError:
        raise SystemExit(
            f"psanalyze emitted non-JSON (rc={p.returncode}):\n"
            f"{p.stdout[:2000]}\n{p.stderr[:2000]}")
    return p.returncode, doc


def seeded_tree(td: str, rule: str, tag: str = "") -> str:
    root = os.path.join(td, rule.replace("-", "_") + tag)
    for d in TREE_DIRS:
        shutil.copytree(
            os.path.join(REPO, d), os.path.join(root, d),
            ignore=shutil.ignore_patterns("__pycache__", "_build",
                                          "results"))
    path, old, new = SEEDS[rule]
    target = os.path.join(root, path)
    with open(target, encoding="utf-8") as f:
        src = f.read()
    if old not in src:
        raise SystemExit(f"seed anchor for {rule} vanished from {path} "
                         "— update tools/analyze_smoke.py")
    with open(target, "w", encoding="utf-8") as f:
        f.write(src.replace(old, new, 1))
    return root


def main() -> int:
    # 1) clean tree: silent, exit 0
    rc, doc = run_psanalyze(REPO)
    assert rc == 0 and doc["finding_count"] == 0, (
        f"psanalyze must be clean on the committed tree, got rc={rc}: "
        f"{doc['findings']}")
    print(f"analyze_smoke: clean tree silent ({len(doc['rules'])} rules)")

    # 2) every rule fires on its seeded defect
    with tempfile.TemporaryDirectory(prefix="psanalyze_smoke_") as td:
        for rule in SEEDS:
            root = seeded_tree(td, rule)
            rc, doc = run_psanalyze(root, rules=[rule])
            hits = [f for f in doc["findings"] if f["rule"] == rule]
            assert rc != 0 and hits, (
                f"rule {rule} stayed silent on its seeded defect "
                f"(rc={rc}, findings={doc['findings']})")
            print(f"analyze_smoke: {rule} fired on seeded defect "
                  f"({hits[0]['path']}:{hits[0]['line']})")

        # pragma allowlist: the same off-thread call, annotated, passes
        root = seeded_tree(td, "thread-affinity", tag="_pragma")
        path = os.path.join(root, SEEDS["thread-affinity"][0])
        with open(path, encoding="utf-8") as f:
            src = f.read()
        with open(path, "w", encoding="utf-8") as f:
            f.write(src.replace(
                "self.core.server._lib.tps_server_pump(self.core.server._h)",
                "self.core.server._lib.tps_server_pump(self.core.server._h)"
                "  # psanalyze: ok thread-affinity"))
        rc, doc = run_psanalyze(root, rules=["thread-affinity"])
        assert rc == 0 and doc["suppressed_count"] >= 1, (
            f"pragma did not suppress the seeded finding: {doc}")
        print("analyze_smoke: pragma suppression honored "
              f"({doc['suppressed_count']} suppressed)")

    # 3) the sanitizer wiring catches a real bug
    from pytorch_ps_mpi_tpu.utils.native import SANITIZE_FLAGS

    with tempfile.TemporaryDirectory(prefix="psanalyze_asan_") as td:
        bug = os.path.join(td, "bug.cpp")
        with open(bug, "w") as f:
            f.write("#include <cstring>\n"
                    "int main(int argc, char**) {\n"
                    "  char* p = new char[8];\n"
                    "  std::memset(p, 0, 8 + argc);  // off the end\n"
                    "  return p[0];\n"
                    "}\n")
        exe = os.path.join(td, "bug")
        subprocess.run(["g++", "-std=c++17", *SANITIZE_FLAGS["asan"],
                        "-o", exe, bug], check=True, timeout=120)
        p = subprocess.run([exe], capture_output=True, text=True,
                           timeout=60)
        assert p.returncode != 0 and "AddressSanitizer" in p.stderr, (
            "ASan flags failed to catch a seeded heap overflow — the "
            f"sanitizer wiring is dead (rc={p.returncode})")
        print("analyze_smoke: ASan wiring caught the seeded "
              "heap-buffer-overflow")

    print("analyze_smoke: all checks green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
