"""AST lint rules for the Pallas GNN stack (+ pytree round-trip check).

Five rules, each encoding an invariant the stack's correctness rests on:

  * **raw-kernel-entry** — the forward-only Pallas entry points
    (``spmm_ell_pallas``, ``gat_ell_pallas``, ``attn_ell_pallas``,
    ``grouped_matmul_pallas``, ``segment_softmax_pallas``,
    ``flash_attention_pallas``) may only be called from inside their own
    kernel package (its ``ops.py`` wrapper is the differentiable,
    budget-checked public surface). A call anywhere else bypasses the
    custom VJP, the SMEM chunking, and the budget validation at once.
    The rule is also *generic*: ANY call named ``*_pallas`` that is not a
    registered entry (or the ``use_pallas``/``forward_only_pallas``
    helpers) must live inside ``repro/kernels/`` — a new raw entry is
    package-private until it is registered here with its owning package.
  * **injectable-clock-rng** — the deterministic host paths
    (``data/resilience.py`` fault handling, ``data/loader.py`` batch
    production, ``data/feature_store.py`` cache eviction,
    ``data/partition.py`` region growing) must stay deterministic and
    testable: no ``time.time()``, no stdlib ``random``, no global-state
    ``np.random.*`` calls, no zero-arg ``default_rng()`` (the injectable
    ``clock=``/``sleep=``/seeded-rng discipline).
  * **host-packing-purity** — the producer-thread packers (CSR->ELL
    packing, grouped-matmul pack plans, slot-bound computation) and the
    loader pipeline's sample/gather stages plus the hot-cache eviction
    must be pure numpy: a ``jnp.``/``jax.`` call there moves device work
    (and possibly tracing) onto the loader's producer/stage threads —
    only ``_stage_pack`` may touch jnp, on purpose.
  * **shard-step-purity** — the ``shard_map``'d train-step bodies
    (``MeshTrainer``'s ``_shard_body``/``_shard_body_compressed``) must
    stay on-device end to end: no ``jax.device_get`` and no host
    callbacks (``pure_callback``/``io_callback``/``debug_callback``/
    ``print``-style debugging). A host round-trip inside the sharded body
    serialises every device on the mesh behind one host transfer — the
    exact sync point data parallelism exists to remove.
  * **pytree-roundtrip** (dynamic, not AST) — every registered pytree
    (``Batch``, ``HeteroBatch``, ``EdgeIndex``) must flatten/unflatten to
    an equal treedef with its aux fields intact, else batches silently
    retrace or drop metadata across the jit boundary.

``python -m repro.analysis`` runs everything over ``src/`` and exits
non-zero on any finding; ``tests/test_static_analysis.py::test_lint_clean``
enforces it in tier 1.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Set, Tuple

# kernel entry name -> kernel package directory (posix fragment) whose
# files may call it (the defining module + its ops.py wrapper).
RAW_KERNEL_ENTRIES: Dict[str, str] = {
    "spmm_ell_pallas": "repro/kernels/spmm/",
    "gat_ell_pallas": "repro/kernels/attention/",
    "attn_ell_pallas": "repro/kernels/attention/",
    "grouped_matmul_pallas": "repro/kernels/grouped_matmul/",
    "segment_softmax_pallas": "repro/kernels/segment_softmax/",
    "flash_attention_pallas": "repro/kernels/flash_attention/",
}

# ``*_pallas`` callables that are NOT raw kernel entries (dispatch helpers).
PALLAS_CALL_ALLOWLIST: Set[str] = {"use_pallas", "forward_only_pallas"}

# path suffix -> function names that must stay jnp/jax-free (producer-thread
# host packing: shape decisions and table packing, pure numpy by contract).
# The loader pipeline's sample/gather stages and the hot-row cache's
# lookup/insert/eviction run on producer/stage threads and obey the same
# contract — only _stage_pack is allowed to touch jnp (device put).
HOST_PACKING_FUNCS: Dict[str, Set[str]] = {
    "repro/kernels/spmm/ops.py": {
        "_ell_positions", "csr_to_ell", "csr_to_ell_bucketed",
        "csr_to_ell_static", "ell_layout_from_bounds", "ell_row_ranges"},
    "repro/kernels/grouped_matmul/ops.py": {"_pack_plan"},
    "repro/data/sampler.py": {"static_slot_bounds"},
    "repro/data/hetero_sampler.py": {
        "hetero_static_slot_bounds", "_stage_sample", "_stage_gather"},
    "repro/data/loader.py": {
        "_stage_sample", "_stage_gather", "_seed_batches", "_seed_route",
        "split_seed_shards", "_sample_one", "_gather_one"},
    "repro/data/feature_store.py": {"lookup", "insert", "_evict", "_get"},
    "repro/data/partition.py": {
        "partition_graph", "_frontier_neighbors", "_undirected_csr"},
}

# Files whose host-side control flow must be deterministic and testable:
# resilience fault paths, the loader's stage pipeline + seed batching, the
# feature-store caches' eviction, and the partitioner's region growing.
DETERMINISTIC_HOST_SUFFIXES: Tuple[str, ...] = (
    "repro/data/resilience.py",
    "repro/data/loader.py",
    "repro/data/feature_store.py",
    "repro/data/partition.py",
)

# backward-compat alias (pre-pipeline rule scope)
RESILIENCE_SUFFIX = DETERMINISTIC_HOST_SUFFIXES[0]

# path suffix -> shard_map'd step-body function names that must stay
# on-device (no host transfers / callbacks inside the mesh step).
SHARD_STEP_FUNCS: Dict[str, Set[str]] = {
    "repro/launch/train.py": {"_shard_body", "_shard_body_compressed"},
}

# Call names (matched on the final attribute) that force a host round-trip.
_HOST_SYNC_CALLS = {"device_get", "pure_callback", "io_callback",
                    "debug_callback", "debug_print"}

# numpy global-state RNG entry points (the seeded-Generator API is fine).
_NP_GLOBAL_RNG = {"seed", "random", "rand", "randn", "randint", "choice",
                  "shuffle", "permutation", "normal", "uniform"}


@dataclasses.dataclass
class Finding:
    path: str
    lineno: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.lineno}: [{self.rule}] {self.message}"


def _posix(path: str) -> str:
    return path.replace(os.sep, "/")


def _attr_chain(node: ast.AST) -> List[str]:
    """``a.b.c`` -> ["a", "b", "c"]; [] when the root is not a Name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return []


def _call_name(node: ast.Call) -> Optional[str]:
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def _lint_raw_kernel_entries(path: str, tree: ast.AST) -> List[Finding]:
    posix = _posix(path)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        allowed = RAW_KERNEL_ENTRIES.get(name or "")
        if allowed and allowed not in posix:
            findings.append(Finding(
                path, node.lineno, "raw-kernel-entry",
                f"{name} is a forward-only raw kernel entry; call the "
                f"differentiable wrapper in {allowed}ops.py instead"))
        elif (name and name.endswith("_pallas")
              and name not in RAW_KERNEL_ENTRIES
              and name not in PALLAS_CALL_ALLOWLIST
              and "repro/kernels/" not in posix):
            findings.append(Finding(
                path, node.lineno, "raw-kernel-entry",
                f"{name} looks like an unregistered raw Pallas entry; raw "
                f"entries are package-private to repro/kernels/ — expose a "
                f"differentiable ops.py wrapper and register the entry in "
                f"RAW_KERNEL_ENTRIES"))
    return findings


def _lint_resilience_clock_rng(path: str, tree: ast.AST) -> List[Finding]:
    if not _posix(path).endswith(DETERMINISTIC_HOST_SUFFIXES):
        return []
    findings = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == "random":
                    findings.append(Finding(
                        path, node.lineno, "injectable-clock-rng",
                        "stdlib random in fault paths: use a seeded "
                        "np.random.default_rng(seed) stream"))
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[0] == "random":
                findings.append(Finding(
                    path, node.lineno, "injectable-clock-rng",
                    "stdlib random in fault paths: use a seeded "
                    "np.random.default_rng(seed) stream"))
        elif isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain == ["time", "time"]:
                findings.append(Finding(
                    path, node.lineno, "injectable-clock-rng",
                    "time.time() in fault paths: use the injectable "
                    "clock=time.monotonic default"))
            elif (len(chain) == 3 and chain[0] in ("np", "numpy")
                  and chain[1] == "random" and chain[2] in _NP_GLOBAL_RNG):
                findings.append(Finding(
                    path, node.lineno, "injectable-clock-rng",
                    f"np.random.{chain[2]} uses the global RNG state: "
                    f"use a seeded default_rng(seed) stream"))
            elif (chain and chain[-1] == "default_rng"
                  and not node.args and not node.keywords):
                findings.append(Finding(
                    path, node.lineno, "injectable-clock-rng",
                    "default_rng() without a seed is nondeterministic: "
                    "thread the component's seed through"))
    return findings


def _lint_host_packing(path: str, tree: ast.AST) -> List[Finding]:
    posix = _posix(path)
    func_names: Optional[Set[str]] = None
    for suffix, names in HOST_PACKING_FUNCS.items():
        if posix.endswith(suffix):
            func_names = names
            break
    if func_names is None:
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in func_names:
            continue
        for sub in ast.walk(node):
            chain = _attr_chain(sub) if isinstance(sub, ast.Attribute) \
                else []
            if chain and chain[0] in ("jnp", "jax"):
                findings.append(Finding(
                    path, sub.lineno, "host-packing-purity",
                    f"{node.name} is producer-thread host packing and must "
                    f"stay pure numpy; found {'.'.join(chain)}"))
                break  # one finding per function is enough signal
    return findings


def _lint_shard_step_purity(path: str, tree: ast.AST) -> List[Finding]:
    posix = _posix(path)
    func_names: Optional[Set[str]] = None
    for suffix, names in SHARD_STEP_FUNCS.items():
        if posix.endswith(suffix):
            func_names = names
            break
    if func_names is None:
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in func_names:
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            name = _call_name(sub)
            if name in _HOST_SYNC_CALLS:
                findings.append(Finding(
                    path, sub.lineno, "shard-step-purity",
                    f"{node.name} is a shard_map'd step body and must stay "
                    f"on-device; {name} forces a host round-trip that "
                    f"serialises the whole mesh"))
    return findings


def lint_source(path: str, source: str) -> List[Finding]:
    """All AST rules over one file's source text."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, "parse-error", str(e))]
    return (_lint_raw_kernel_entries(path, tree)
            + _lint_resilience_clock_rng(path, tree)
            + _lint_host_packing(path, tree)
            + _lint_shard_step_purity(path, tree))


def lint_tree(root: str) -> List[Finding]:
    """Run the AST rules over every ``.py`` under ``root``."""
    findings: List[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as fh:
                findings.extend(lint_source(path, fh.read()))
    return findings


# ------------------------------------------------------- pytree round-trip
def _roundtrip(obj, describe: str) -> List[Finding]:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(obj)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    leaves2, treedef2 = jax.tree_util.tree_flatten(rebuilt)
    findings = []
    if treedef != treedef2:
        findings.append(Finding(
            describe, 0, "pytree-roundtrip",
            f"treedef not stable under flatten/unflatten:\n  was "
            f"{treedef}\n  now {treedef2}"))
    if len(leaves) != len(leaves2):
        findings.append(Finding(
            describe, 0, "pytree-roundtrip",
            f"leaf count changed {len(leaves)} -> {len(leaves2)}"))
    return findings


def check_pytree_roundtrips() -> List[Finding]:
    """Flatten/unflatten every registered pytree; aux must survive intact.

    Treedef equality covers the aux data (it is part of the treedef), so a
    flatten/unflatten pair that drops or reorders aux fields fails here.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.edge_index import EdgeIndex
    from repro.data.hetero_sampler import HeteroBatch
    from repro.data.loader import Batch

    rng = np.random.default_rng(0)
    src = rng.integers(0, 8, 16).astype(np.int32)
    dst = rng.integers(0, 8, 16).astype(np.int32)
    ei = EdgeIndex.from_coo(src, dst, 8, 8).sort_by("col")[0].fill_cache()
    findings = _roundtrip(ei, "EdgeIndex")

    batch = Batch(
        x=jnp.zeros((8, 4)), edge_index=ei,
        n_id=jnp.arange(8), e_id=jnp.arange(16),
        seed_slots=jnp.arange(2), num_sampled_nodes=[2, 6],
        num_sampled_edges=[16], y=jnp.zeros((2,)),
        extras={"tag": jnp.zeros(())})
    findings += _roundtrip(batch, "Batch")

    et = ("user", "buys", "item")
    hetero = HeteroBatch(
        x_dict={"user": jnp.zeros((4, 2)), "item": jnp.zeros((6, 2))},
        edge_index_dict={et: ei},
        n_id_dict={"user": jnp.arange(4), "item": jnp.arange(6)},
        e_id_dict={et: jnp.arange(16)},
        seed_slots=jnp.arange(2), seed_type="item",
        num_sampled_nodes_dict={"user": [4], "item": [2, 4]},
        num_sampled_edges_dict={et: [16]},
        y=jnp.zeros((2,)))
    findings += _roundtrip(hetero, "HeteroBatch")
    leaves, treedef = jax.tree_util.tree_flatten(hetero)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    if (rebuilt.seed_type != hetero.seed_type
            or rebuilt.num_sampled_nodes_dict != hetero.num_sampled_nodes_dict
            or rebuilt.num_sampled_edges_dict
            != hetero.num_sampled_edges_dict):
        findings.append(Finding(
            "HeteroBatch", 0, "pytree-roundtrip",
            "aux fields (seed_type / per-hop counts) did not round-trip"))
    return findings


def run_all(root: str) -> List[Finding]:
    """AST rules over ``root`` plus the dynamic pytree round-trip checks."""
    return lint_tree(root) + check_pytree_roundtrips()
