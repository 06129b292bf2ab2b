"""A model family comes to the harness as files. In a copy of
``BENCHMARK.json`` and ``chipbench/`` a second family, a configuration that
names it and a cell of each kind are added (files, and entries in
``BENCHMARK.json``), and both cells run through the rehearsal's own entry
with no file that was there changed. A tree shaped like a mixture of
experts goes through everything that handles weights by leaf. A
configuration that names no family, or one that is not there, gives no
result."""
import copy
import filecmp
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import check, spec, train, weights
from chipbench.weights import Leaf

OTHER_FAMILY = '''"""A second family, brought as one new file: the Llama family's answers
under another name."""
from chipbench.families.llama import *  # noqa: F401,F403
'''

CELLS = {"other.train": "pretrain-4k", "other.serve": "chat-open",
         "nofamily.train": "pretrain-4k", "unknown.train": "pretrain-4k"}


def _files(root):
    skip = ("__pycache__", ".chipbench_out", ".pytest_cache")
    out = []
    for d, dirs, names in os.walk(root):
        dirs[:] = [x for x in dirs if x not in skip]
        out += [os.path.relpath(os.path.join(d, n), root) for n in names]
    return sorted(out)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The copy, with what a PR that brings a family would add to it."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(spec.HERE, os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  ".chipbench_out"))
    bench = spec.benchmark()
    before = _files(os.path.join(root, "chipbench"))
    here = os.path.join(root, "chipbench")
    with open(os.path.join(here, "families", "other.py"), "w") as f:
        f.write(OTHER_FAMILY)
    with open(os.path.join(here, "configs", "yi-1.5-9b.json")) as f:
        model = json.load(f)
    added = copy.deepcopy(bench)
    for name, fam in (("other", "other"), ("nofamily", None),
                      ("unknown", "not-there")):
        m = dict(model)
        del m["family"]
        if fam is not None:
            m["family"] = fam
        path = f"chipbench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(m, f)
        added["configs"].append(dict(bench["configs"][0], name=name,
                                     file=path))
    for cell, mix in CELLS.items():
        added["workloads"].append({
            "name": cell, "config": cell.split(".")[0], "traffic": mix,
            "chips": 1, "why": "a cell added by files alone"})
        kind = "train" if mix == "pretrain-4k" else "serve_open"
        like = next(w["name"] for w in bench["workloads"]
                    if spec.load_cell(w["name"]).traffic["kind"] == kind)
        for m in added["end_to_end"] + added["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(added, f)
    return root, before


def rehearse(root, cell):
    """The rehearsal's own entry, run from where the copy lies; the program
    under test is this repo's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell,
         "--rehearse", "--seed", "2147483777", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", ["other.train", "other.serve"])
def test_a_family_is_added_by_files_alone(checkout, cell):
    root, before = checkout
    run = rehearse(root, cell)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device",
            "compared"} <= set(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["rehearsal_values"]
    assert all(v["value"] <= v["limit"] for v in line["compared"].values()), \
        line["compared"]
    # no file that was there differs, and none went
    here = os.path.join(root, "chipbench")
    same, differ, errors = filecmp.cmpfiles(spec.HERE, here, before,
                                            shallow=False)
    assert (differ, errors) == ([], []) and same == before
    assert set(_files(here)) - set(before) == {
        "families/other.py", "configs/other.json", "configs/nofamily.json",
        "configs/unknown.json"}


@pytest.mark.parametrize("cell, named", [("nofamily.train", "None"),
                                         ("unknown.train", "'not-there'")])
def test_no_family_no_result(checkout, cell, named):
    run = rehearse(checkout[0], cell)
    assert run.returncode != 0
    assert run.stdout.strip() == ""
    assert f"family {named}" in run.stderr
    assert "['llama', 'other']" in run.stderr      # the families found


# -- a tree that is not the dense decoder's ----------------------------------------

def expert_leaves(m):
    """Shaped like a mixture of experts: a router and its zero-started bias,
    expert stacks of one more axis, one dense layer's leaves beside them in
    a list, so that nothing stacks into one ``(layers, ...)`` leaf."""
    h, i, v, n, e = m["h"], m["i"], m["v"], m["layers"], m["experts"]
    return {
        "embed": Leaf((v, h)),
        "dense": [{"norm": Leaf((h,), "one"), "up": Leaf((h, 4 * i)),
                   "down": Leaf((4 * i, h))}],
        "moe": {"norm": Leaf((n, h), "one"), "router": Leaf((n, h, e)),
                "router_bias": Leaf((n, e), "zero"),
                "experts": {"up": Leaf((n, e, h, i)),
                            "down": Leaf((n, e, i, h))}},
        "head": Leaf((h, v)),
    }


SMALL = {"h": 16, "i": 32, "v": 64, "layers": 2, "experts": 4}


def test_expert_shaped_tree_goes_through_by_path():
    import jax
    import jax.numpy as jnp
    leaves = expert_leaves(SMALL)
    w = weights.make_weights(leaves, 2147483777)
    names = train.flat_names(w)
    assert sorted(names) == [
        "dense/0/down", "dense/0/norm", "dense/0/up", "embed", "head",
        "moe/experts/down", "moe/experts/up", "moe/norm", "moe/router",
        "moe/router_bias"]
    for leaf, a in zip(
            jax.tree_util.tree_leaves(leaves, is_leaf=weights.is_leaf),
            jax.tree_util.tree_leaves(w)):
        assert a.shape == leaf.shape and a.dtype == jnp.bfloat16
    assert np.all(np.asarray(names["dense/0/norm"], np.float32) == 1)
    assert np.all(np.asarray(names["moe/router_bias"], np.float32) == 0)
    up = np.asarray(names["moe/experts/up"], np.float32)
    assert 0.015 < up.std() < 0.025 and abs(up.mean()) < 0.003
    # every leaf has a key of its own, and the seed alone decides them
    assert not np.array_equal(up[0, 0], up[0, 1])
    again = train.flat_names(weights.make_weights(leaves, 2147483777))
    other = train.flat_names(weights.make_weights(leaves, 2147483778))
    assert all(np.array_equal(names[k], again[k]) for k in names)
    assert not np.array_equal(names["moe/router"], other["moe/router"])

    # the comparison by worst leaf, on norms keyed by the same paths
    norm = lambda t: {k: float(jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32))))) for k, a in train.flat_names(t).items()}
    ref = norm(w)
    assert ref["moe/router_bias"] == 0.0
    assert check.moved_leaves(ref) == [k for k in ref
                                       if k != "moe/router_bias"]
    assert check.worst_leaf_gap(ref, ref) == (0.0, "")
    prog = dict(ref, **{"moe/experts/down": 2 * ref["moe/experts/down"]})
    gap, at = check.worst_leaf_gap(prog, ref, check.moved_leaves(ref))
    assert at == "moe/experts/down" and gap == pytest.approx(1.0)


def test_a_leaf_starts_in_a_known_way():
    with pytest.raises(ValueError):
        weights.make_weights({"w": Leaf((2, 2), "uniform")}, 0)
