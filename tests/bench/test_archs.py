"""The arch families behind ``bench.archs.family``: the ``dense_ln``
family's weights and reference logits are pinned bit for bit, only the
lookup names a family's modules, no family's reference imports the
program, and a family that lacks a function is refused."""

import hashlib
import os
import pathlib
import re
import shutil

import jax
import numpy as np
import pytest

import tiny
from bench.archs import family

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARCHS = ROOT / "bench" / "archs"

# Recorded from the tiny configuration before the dense_ln code moved
# under bench/archs/ (CPU): the sha256 of every weight leaf's path and
# bytes in tree order, and of the reference's float32 logits at the six
# served positions of a 40-token prompt, in each mode.
PIN_SEED = 2 ** 31 + 2024
WEIGHTS_SHA256 = \
    "34b1efee086082e941aae29e47d2603b74b449fa4d32cac2ed43677a30538f09"
LOGITS_SHA256 = {
    "float32":
        "308dbcd1d96596863f393c8b3a778c6faa0ee0ac4905fcc7a091c0d70a1cf193",
    "float8":
        "c31d9b845b3b0e0d11acd78a32f7c4904dc9dd9cbe72ddd0e419c6d761011f6d",
}
# Three of the float32 logits, for a readable failure.
LOGITS_AT = {(0, 0): -1.2057721614837646, (3, 1): 1.2271281480789185,
             (5, 2): 0.2782957851886749}


@pytest.fixture(scope="module")
def pinned_params():
    return family(tiny.CONF).model.make_params(tiny.CONF, PIN_SEED)


def test_dense_ln_weights_are_pinned(pinned_params):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(pinned_params):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


@pytest.mark.parametrize("mode", sorted(LOGITS_SHA256))
def test_dense_ln_reference_logits_are_pinned(pinned_params, mode):
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 256, 40).astype(np.int32)
    served = rng.integers(0, 256, 6).astype(np.int32)
    out = family(tiny.CONF).reference.logits_at(
        tiny.CONF, pinned_params, prompt, served, 256, 8, mode)
    assert out.shape == (6, 256) and out.dtype == np.float32
    if mode == "float32":
        assert {k: float(out[k]) for k in LOGITS_AT} == LOGITS_AT
    digest = hashlib.sha256(np.ascontiguousarray(out).tobytes()).hexdigest()
    assert digest == LOGITS_SHA256[mode]


# ``import bench.model`` and the like, and any ``bench.archs.<family>``
# module, named outside bench/archs/.
FAMILY_IMPORT = re.compile(
    r"^\s*(from|import)\s+bench\.(model|reference|work)\b"
    r"|^\s*(from|import)\s+bench\.archs\.\w+"
    r"|^\s*from\s+bench\s+import\s+.*\b(model|reference|work)\b",
    re.M)


def test_only_the_lookup_names_a_family_module():
    found = []
    for d in (ROOT / "bench", ROOT / "tests" / "bench"):
        for f in d.rglob("*.py"):
            if ARCHS in f.parents:
                continue
            found += [f"{f.relative_to(ROOT)}: {m.group(0).strip()}"
                      for m in FAMILY_IMPORT.finditer(f.read_text())]
    assert found == []


def test_no_family_reference_imports_the_program():
    refs = sorted(ARCHS.glob("*/reference.py"))
    assert refs
    for f in refs:
        assert not re.search(r"^\s*(from|import)\s+repro\b", f.read_text(),
                             re.M), f


def test_a_family_lacking_a_function_is_refused():
    tag = f"partial{os.getpid()}"
    pkg = ARCHS / tag
    try:
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "model.py").write_text("def program_config(conf):\n"
                                      "    return None\n")
        (pkg / "reference.py").write_text("")
        (pkg / "work.py").write_text("")
        with pytest.raises(AttributeError, match="model.make_params"):
            family({"arch_family": tag})
    finally:
        shutil.rmtree(pkg, ignore_errors=True)
