"""Every Pallas kernel of the program is named, and of those names only the
fused update's matches the pattern by which ``update_roofline`` finds its
ops in a trace (XLA:TPU names a Mosaic call ``%<name>.<n>``)."""
import ast
import importlib.util

from bench import cells


def _pallas_names():
    """(file, name keyword) of every ``pallas_call`` in the program."""
    for path in sorted((cells.ROOT / "src" / "repro").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) and "pallas_call" in (
                    getattr(node.func, "attr", None),
                    getattr(node.func, "id", None)):
                kw = {k.arg: k.value for k in node.keywords}
                yield path.name, kw.get("name")


def _update_kernel():
    spec = importlib.util.spec_from_file_location(
        "update_roofline", cells.BENCH / "metrics" / "update_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


def test_every_pallas_call_is_named():
    calls = list(_pallas_names())
    assert len(calls) >= 6
    assert all(isinstance(n, ast.Constant) and n.value for _, n in calls), \
        calls


def test_only_the_fused_update_matches_the_update_reader():
    kernel = _update_kernel()
    matched = {(f, n.value) for f, n in _pallas_names() if kernel.match(
        f"%{n.value}.3 = (bf16[8,128]{{1,0}}) custom-call(%a, %b)")}
    assert matched == {("fused_update.py", "fused_update")}
