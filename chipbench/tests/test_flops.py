"""Operation counts against hand counts at smoke sizes and against the
counts of the published configurations, and the benchmark's copy of the
pruning rule against the program's own list."""

import jax
import pytest

import smoke
from chipbench import spec
from chipbench.families import decoder_lm as D
from chipbench.families import flops as FL


def test_train_counts_by_hand():
    # per layer q 64x64, k 64x32, v 64x32, o 64x64, experts 3 x 64x32 at
    # top-2, router 64x8 (dense); 2 layers; tied head 64 x 512; seq 64
    per = D.train_flops_per_token(smoke.GRANITE, 64, 2, 8)
    attn_lin = 1.5 * 2 * (4096 + 2048 + 2048 + 4096) * 2        # 73728
    experts = 1.5 * 2 * 3 * 2048 * 2 * 2                       # 73728
    router = 6 * 64 * 8 * 2                                    # 6144
    head = 6 * 64 * 512                                        # 196608
    attn = 6 * 64 * 4 * 16 * 2                                 # 49152
    assert per["sparse"] == attn_lin + experts + router + head + attn == 399360
    assert per["dense"] == 546816


@pytest.mark.parametrize("name,sparse,dense", [
    ("granite-moe-1b-a400m-l8", 882395136, 1259882496),
    ("qwen3-8b-l2-v18992", 1825701888, 2983329792)])
def test_published_counts_stay(name, sparse, dense):
    """The counts per token at seq 4096 that the cells' MFU has read since
    the benchmark began, reached through the configuration's family."""
    conf = spec.config(name)
    assert spec.family(conf).train_flops_per_token(conf, 4096, 2, 8) == {
        "sparse": sparse, "dense": dense}


@pytest.mark.parametrize("conf", [smoke.GRANITE, smoke.QWEN], ids=["granite", "qwen"])
def test_pruning_rule_matches_the_program(conf):
    from repro.analysis.graph_audit import prunable_sites
    from repro.core.sparsity import SparsityConfig
    from repro.models import transformer_lm as T
    from repro.optim import sgd
    from chipbench.train_cell import leaf_names

    cfg = D.program_config(conf)
    master = T.init(jax.random.PRNGKey(0), cfg, abstract=True)[0]
    sp = SparsityConfig(n=2, m=8, method="bdwp")
    names = leaf_names(master)
    shapes = dict(zip(names, (x.shape for x in jax.tree.leaves(master))))
    ours = {n for n in names
            if FL.pruned(n, sgd._logical_shape(n, shapes[n])[0], 8)}
    assert ours == set(prunable_sites(master, sp))
    # every linear the counts use is a leaf of that shape in the program
    for name, k, f, _ in D.linears(D.model(conf)):
        assert shapes[name][-2:] == (k, f), name
    assert ours <= {n for n, *_ in D.linears(D.model(conf))}
