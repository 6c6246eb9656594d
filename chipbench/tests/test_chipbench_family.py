"""A configuration's architecture is found by file (``families/<name>.py``),
and the Qwen2 family makes the same weights and reference logits, bit for
bit, as the harness made before the family module held them."""
import hashlib
import json

import jax
import numpy as np
import pytest

from chipbench import cell as C

SEED = 2**31 + 12345
# checksums of a tiny Qwen2 (two layers, d 64, a 512-token vocabulary) made
# from each configuration file, recorded at commit 673caec, when
# ``weights.engine_params`` and ``reference.Reference`` held this code:
# (served weights, float32 reference logits, fp8 control logits).  XLA's CPU
# backend sums in another order in a process held to a single core, so the
# logits' checksums hold with two cores or more.
PINNED = {"qwen2-0.5b": ("a601b6bdb49aac21", "4928dc22ccdb7b94",
                         "c722b463a030f216"),
          "qwen2-7b": ("9e6f11676d8ab90a", "99f57784e6215775",
                       "af13ca4dd40fd2d6")}
SEQS = [(list(range(5, 45)), 9),
        ([(i * 37 + 11) % 512 for i in range(270)], 20)]


def conf(name):
    return json.loads((C.HERE / "configs" / f"{name}.json").read_text())


def digest(named_arrays):
    h = hashlib.sha256()
    for name, a in named_arrays:
        a = np.asarray(a)
        h.update(f"{name}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_family_found_by_architecture(name, tmp_path):
    fam = C.family(conf(name))
    assert fam.__file__ == str(C.HERE / "families" / "qwen2.py")
    unknown = dict(conf(name), architectures=["NoSuchModelForCausalLM"])
    with pytest.raises(FileNotFoundError,
                       match=str(C.HERE / "families" / "nosuchmodel.py")):
        C.family(unknown)
    with pytest.raises(FileNotFoundError,
                       match=str(tmp_path / "families" / "qwen2.py")):
        C.family(conf(name), here=tmp_path)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weights_and_reference_bit_identical(name):
    c = conf(name)
    c.update(name="tiny", hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2,
             num_hidden_layers=2, vocab_size=512)
    fam = C.family(c)
    leaves = jax.tree_util.tree_flatten_with_path(fam.engine_params(SEED, c))
    weights = digest((jax.tree_util.keystr(p), a) for p, a in leaves[0])
    logits = digest(enumerate(fam.Reference(c).logits(SEED, SEQS)))
    control = digest(enumerate(
        fam.Reference(c, quant="fp8").logits(SEED, SEQS)))
    assert (weights, logits, control) == PINNED[name]
