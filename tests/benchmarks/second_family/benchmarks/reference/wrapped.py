"""Plain reference of the second family: the repository's Baichuan
reference under this family's own leaf names (``blocks.<i>.*``,
``norm_out.scale``, ``unembed``), in the same order, so the seeded values
are the same (``lib/weights.py`` draws by position and scales by the
naming rules, which these names fall under alike)."""

from benchmarks.reference import baichuan as base

make_batch = base.make_batch


def to_base(name):
    if name.startswith("blocks."):
        return "layers." + name[len("blocks."):]
    return {"norm_out.scale": "ln_f.scale", "unembed": "head"}.get(name,
                                                                   name)


def from_base(name):
    if name.startswith("layers."):
        return "blocks." + name[len("layers."):]
    return {"ln_f.scale": "norm_out.scale", "head": "unembed"}.get(name,
                                                                   name)


def weight_shapes(cfg, layers):
    return {from_base(n): s
            for n, s in base.weight_shapes(cfg, layers).items()}


def _renamed(w):
    return {to_base(n): a for n, a in w.items()}


def logits_at(w, tokens, rows, cfg, layers, quant=None):
    return base.logits_at(_renamed(w), tokens, rows, cfg, layers, quant)


def batch_loss(w, batch, cfg, layers, quant=None, **blocks):
    return base.batch_loss(_renamed(w), batch, cfg, layers, quant, **blocks)
