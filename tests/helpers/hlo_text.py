"""An optimized HLO module's text without what only says where the code
stood and what it was called: every ``metadata={...}`` and the four
tables of file names, function names, locations and stack frames at the
head. What is left is the program; two such texts are equal or the
program moved (ISSUE 36: a ``jax.named_scope`` is metadata and nothing
else)."""

import re

from helpers.families import program

_TABLE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
    r"(?:.+\n)*\n?", re.M)


def program_only(hlo: str) -> str:
    """Instructions and computations are numbered anew in their order of
    appearance: the compiler's ``.1147`` suffixes count what the unoptimized
    module held (an inner jitted function lowered once a name stack), not
    what the program is."""
    text = _TABLE.sub("", re.sub(r",? ?metadata=\{[^{}]*\}", "", hlo))
    ids: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: ids.setdefault(m.group(), f"%{len(ids)}"), text)


def assert_scopes_are_metadata(family: str) -> None:
    """The family's compiled step (``families.program``) and the same step
    built again with every ``jax.named_scope`` a null context are one
    program once ``metadata={...}`` is taken out."""
    named = program_only(program(family).hlo)
    assert re.search(r"\bds\.[a-z_]+", named) is None     # all metadata
    assert program_only(program(family, patch="bare").hlo) == named


def assert_scope_is_the_kernels(hlo: str, scope: str, stem: str, mixer: str,
                                others) -> None:
    """In a compiled step, ``scope`` holds the two kernels ``<stem>fwd`` /
    ``<stem>bwd`` and nothing else (interpreted on a CPU, so every
    instruction of a kernel's body carries its name): the forward under
    fwd: and, run again by remat, under bwd:, the backward under bwd:;
    inside ``mixer`` but for the few per cent of a kernel's constants that
    its one trace a shape names by ``scope`` alone, and never inside one
    of the ``others``."""
    held = [m.group(1) for m in re.finditer(
        rf'op_name="([^"]*{re.escape(scope)}\b[^"]*)"', hlo)]
    sides = {(k, "bwd" if "transpose(" in name else "fwd")
             for name in held
             for k in re.findall(rf"{stem}(?:fwd|bwd)", name)[:1]
             if mixer in name}
    assert all(stem in name for name in held), [
        name for name in held if stem not in name][:3]
    assert sides == {(f"{stem}fwd", "fwd"), (f"{stem}fwd", "bwd"),
                     (f"{stem}bwd", "bwd")}
    assert sum(f"/{mixer}/" in name for name in held) > 0.9 * len(held)
    assert not any(o in name for name in held for o in others)


def assert_conv_scope_is_the_kernels(hlo: str, mixer: str, others) -> None:
    """ISSUE 43: scope ``ds.conv`` holds the short convolution's two
    kernels and nothing else, inside ``mixer`` (``ds.kda`` | ``ds.mamba``
    | ``ds.gdn``)."""
    assert_scope_is_the_kernels(hlo, "ds.conv", "ds_short_conv_", mixer,
                                others)


def assert_gated_norm_scope_is_the_kernels(hlo: str, mixer: str,
                                           others) -> None:
    """ISSUE 55: scope ``ds.mix_post`` inside ``mixer`` (``ds.kda`` |
    ``ds.gdn``) holds the gated norm's two kernels and no other leaf op."""
    assert_scope_is_the_kernels(hlo, "ds.mix_post", "ds_gated_norm_", mixer,
                                others)
