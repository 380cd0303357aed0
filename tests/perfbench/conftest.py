"""The ONE place a test of this directory takes this repository's
BENCHMARK.json and perfbench/ tree from: the fixture `root`, which
every such test runs under twice, on `ours` (the repository) and on
`grown` (a copy that later PRs' additions were made to by new files and
appended entries alone: `additions.py`). A new test file brings no
root of its own: it asks for `root` and passes it on
(`cell.benchmark(root)`, `cell.load_cell(name, root)`, ...), and
`test_perfbench_rehearsal.py`'s guard refuses one that does not. So a
pin of today's census fails in the PR that writes it.

A test that also builds or runs a model, or starts the command, does
that on one root: `@pytest.mark.parametrize("root", ["ours"],
indirect=True)`. A test over the entries of a group of BENCHMARK.json
is marked `entries("<group>", ...)` and takes `root` and `entry`: each
root's own entries, the grown root's appended ones among them."""
import functools

import pytest

import additions
from perfbench.harness import cell

ROOTS = ("ours", "grown")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "entries(*groups, roots=ROOTS): run the test once for "
        "each entry of those groups of each root's BENCHMARK.json")


@pytest.fixture(scope="session")
def grown(tmp_path_factory):
    """The grown root, built once a session; a test that rewrites a
    root copies this one."""
    return additions.build(cell.ROOT, tmp_path_factory.mktemp("grown"))


@pytest.fixture
def root(request):
    """`request.param` comes from `pytest_generate_tests`, below."""
    if request.param == "ours":
        return cell.ROOT
    return request.getfixturevalue("grown")


@functools.lru_cache(maxsize=None)
def _bench(name):
    """Entries to parametrise over while collecting, when no fixture has
    run yet: `grow` is what the fixture writes out."""
    ours = cell.benchmark()
    return ours if name == "ours" else additions.grow(ours)


def _parametrizes_root(mark):
    names = mark.args[0]
    if isinstance(names, str):
        names = [n.strip() for n in names.split(",")]
    return "root" in names


def pytest_generate_tests(metafunc):
    """Every test that asks for `root` runs on both roots, unless its
    own `parametrize` says which; an `entries` test once an entry."""
    if "root" not in metafunc.fixturenames:
        return
    mark = metafunc.definition.get_closest_marker("entries")
    if mark is not None:
        metafunc.parametrize(
            ("root", "entry"),
            [pytest.param(name, entry, id=f"{name}-{entry['name']}")
             for name in mark.kwargs.get("roots", ROOTS)
             for group in mark.args for entry in _bench(name)[group]],
            indirect=["root"])
    elif not any(map(_parametrizes_root,
                     metafunc.definition.iter_markers("parametrize"))):
        metafunc.parametrize("root", ROOTS, indirect=True)
