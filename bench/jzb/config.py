"""A configuration file (``bench/configs/<name>.json``) as the harness and
the reference use it.

``ModelCfg`` is the hashable view the jitted weight maker and the
reference take as a static argument. ``check_served`` compares it with
the configuration the program reports it serves, so a run can never
measure other widths than the file states.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Field:
    name: str
    vocab: int
    bag: int = 1
    combiner: str = "sum"


@dataclass(frozen=True)
class ModelCfg:
    model: str
    embed_dim: int
    seq_len: int
    user_fields: tuple
    item_fields: tuple
    attn_mlp: tuple = ()
    mlp: tuple = ()
    gru_dim: int = 0
    table_std: float = 0.1      # weights the benchmark makes: N(0, std^2)
    bias_std: float = 0.05

    @property
    def side_item_fields(self) -> tuple:
        return tuple(f for f in self.item_fields if f.name != "item_id")


def model_cfg(cfg: dict) -> ModelCfg:
    w = cfg["weights"]
    return ModelCfg(
        model=cfg["model"], embed_dim=cfg["embed_dim"],
        seq_len=cfg["seq_len"],
        user_fields=tuple(Field(**f) for f in cfg["user_fields"]),
        item_fields=tuple(Field(**f) for f in cfg["item_fields"]),
        attn_mlp=tuple(cfg.get("attn_mlp", ())),
        mlp=tuple(cfg.get("mlp", ())), gru_dim=cfg.get("gru_dim", 0),
        table_std=w["table_std"], bias_std=w["bias_std"])


def _sizes(c) -> dict:
    """The sizes of a model config: this file's ``ModelCfg`` or the
    program's ``RecsysConfig`` (same attribute names)."""
    def fields(fs):
        return tuple((f.name, f.vocab, f.bag, f.combiner) for f in fs)
    return dict(model=c.model, embed_dim=c.embed_dim, seq_len=c.seq_len,
                user_fields=fields(c.user_fields),
                item_fields=fields(c.item_fields),
                attn_mlp=tuple(c.attn_mlp), mlp=tuple(c.mlp),
                gru_dim=c.gru_dim)


def check_served(mc: ModelCfg, served) -> None:
    """Raise unless the program's model config has exactly these sizes."""
    want, got = _sizes(mc), _sizes(served)
    diff = {k: (want[k], got[k]) for k in want if want[k] != got[k]}
    if diff:
        raise SystemExit(f"the program serves other sizes than the "
                         f"configuration file states: {diff}")
