"""Every configuration field is read by the program: a field that no code
outside ``configs/`` reads is an option that is accepted and does
nothing."""
import dataclasses
import pathlib
import re

import pytest

from repro.configs import base

SRC = pathlib.Path(base.__file__).resolve().parents[1]
# fields no program code reads, each with the reason it stays
UNREAD = {
    # the benchmark's configuration files state it and compare it
    ("ModelConfig", "hybrid_attn_every"),
}


def _program_text() -> str:
    return "\n".join(p.read_text() for p in sorted(SRC.rglob("*.py"))
                     if "configs" not in p.relative_to(SRC).parts)


@pytest.mark.parametrize("cls", [
    base.ModelConfig, base.MoEConfig, base.MLAConfig, base.SSMConfig,
    base.RunConfig, base.ShapeConfig], ids=lambda c: c.__name__)
def test_every_field_is_read(cls):
    text = _program_text()
    unread = [f.name for f in dataclasses.fields(cls)
              if (cls.__name__, f.name) not in UNREAD
              and not re.search(rf"\.{f.name}\b", text)]
    assert not unread, f"{cls.__name__} fields no code reads: {unread}"
