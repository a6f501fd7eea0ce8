"""The compiled program's digest leaves out what only says where the
program came from: op metadata, the stack-frame tables and the source
locations inside a Pallas kernel's serialized body.  Two checkouts at
different paths, or with moved lines, give the same digest for the same
program, and another program gives another."""
import base64
import io
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.scoped import program_digest  # noqa: E402


def _body(op):
    """A kernel body as the TPU compiler's text carries it: MLIR bytecode
    of a module, in base64."""
    from jaxlib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse("module @k { " + op + " }")
        out = io.BytesIO()
        module.operation.write_bytecode(out)
    return base64.b64encode(out.getvalue()).decode()


def _program(path, line, value=1, op_name="jit(step)/aggregate/add"):
    body = _body(f'"x.op"() {{v = {value} : i32}} : () -> () '
                 f'loc("{path}/kernels/k.py":{line}:3)')
    return (
        "HloModule jit_step, is_scheduled=true\n\n"
        "FileNames\n"
        f'1 "{path}/launch/train.py"\n\n'
        "StackFrames\n"
        f"1 {{file_location_id={line} frame_id=0}}\n\n"
        "ENTRY %main.3 (p.1: f32[4]) -> f32[4] {\n"
        "  %p.1 = f32[4]{0} parameter(0)\n"
        "  ROOT %k.2 = f32[4]{0} custom-call(%p.1), "
        'custom_call_target="tpu_custom_call", '
        f'metadata={{op_name="{op_name}" stack_frame_id=1}}, '
        f'backend_config={{"custom_call_config":{{"body":"{body}"}}}}\n'
        "}\n")


def test_digest_ignores_where_the_program_came_from():
    a = _program("/checkout/a/src/repro", 10)
    b = _program("/elsewhere/src/repro", 42, op_name="jit(step)/x/add")
    assert a != b
    assert program_digest(a) == program_digest(b)


def test_digest_tells_programs_apart():
    a = _program("/checkout/a/src/repro", 10)
    assert program_digest(a) != program_digest(
        _program("/checkout/a/src/repro", 10, value=2))
    assert program_digest(a) != program_digest(a.replace("f32[4]", "f32[8]"))
