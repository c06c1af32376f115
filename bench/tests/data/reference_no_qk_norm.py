"""A plain reference that departs from the program: bench/reference.py's
dense decoder without the q/k RMSNorm.  The program keeps the norm, so a
configuration that names this reference has to read not correct."""

import modules

_dense = modules.load("reference")


def logits_at(cfg, params, seqs, positions, **kw):
    return _dense.logits_at(dict(cfg, model_type="llama"), params, seqs,
                            positions, **kw)
