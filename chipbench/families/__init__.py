"""Model families: what the harness needs to know of a kind of model.

A configuration file names its family (``"family": "decoder_lm"``), and
the harness imports ``chipbench/families/<family>.py`` by that name
(``spec.family``).  A family module gives three functions:

* ``program_config(conf)``: the program's model config for the file,
  with the file's cuts; it raises where the program and the file
  disagree on any other size;
* ``train_readings(conf, key, batches, *, n, m, lr, momentum,
  weight_decay, lam, control=False, drop_half=False)``: the plain
  float32 reference's losses and per-leaf first-gradient and change
  norms over the first steps (``control``: in the precision below the
  configuration's; ``drop_half``: on half of each batch);
* ``train_flops_per_token(conf, seq, n, m)``: {"sparse", "dense"}
  operations a training token needs.

A new family is a new file here.  ``reference.py`` (precision, N:M
masks, the BDWP linear, attention, routing capacity, the loss and the
training steps) and ``flops.py`` (the pruning rule) hold what families
share; ``decoder_lm.py``'s sizes, initialisation and layers can be
imported too.
"""
