"""A model family that only the tests use: ``decoder_lm`` wrapped under
another name, recording which of its functions the harness calls.  The
harness finds it by the name in a configuration file once the tests put
this directory on ``chipbench.families.__path__``."""

from chipbench.families import decoder_lm as D

CALLS = set()


def program_config(conf):
    CALLS.add("program_config")
    return D.program_config(conf)


def train_readings(conf, key, batches, **kw):
    CALLS.add("train_readings")
    return D.train_readings(conf, key, batches, **kw)


def train_flops_per_token(conf, seq, n, m):
    CALLS.add("train_flops_per_token")
    return D.train_flops_per_token(conf, seq, n, m)
