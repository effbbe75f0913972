"""The token family's test cell, and the token family in the harness's
place, for the benchmark's tests and the chip witness of the reference's
room (``tests/room_witness.py``)."""
import contextlib
import copy
import json
import os
import tempfile

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
WORKLOAD = "resnet20-cifar10.cross_device"
TOKENS = "tokens-tiny.cross_device"
TOKEN_FAMILY = os.path.join(HERE, "families", "tokens.py")
DATA = os.path.join(HERE, "data")


@contextlib.contextmanager
def token_family():
    """A families directory with the benchmark's families and the token
    family, in the harness's place while the context lasts."""
    saved = harness.FAMILIES
    with tempfile.TemporaryDirectory() as where:
        for name in os.listdir(saved):
            os.symlink(os.path.join(saved, name), os.path.join(where, name))
        os.symlink(TOKEN_FAMILY, os.path.join(where, "tokens.py"))
        harness.FAMILIES = where
        try:
            yield
        finally:
            harness.FAMILIES = saved


def token_cell(pins=None, shrink=None) -> dict:
    """The token family's cell: its configuration under the ResNet
    cell's traffic mix (with ``pins`` added), with its limits
    (``data/limits/``), cut by ``shrink`` where one is given."""
    base = harness.load_cell(WORKLOAD)
    with open(os.path.join(DATA, "tokens-tiny.json")) as f:
        config = json.load(f)
    with open(os.path.join(DATA, "limits", f"{TOKENS}.json")) as f:
        limits = json.load(f)
    mix = copy.deepcopy(base["mix"])
    mix["pins"].update(pins or {})
    cell = dict(base, name=TOKENS, config=config, mix=mix, limits=limits)
    return shrink(cell) if shrink else cell
