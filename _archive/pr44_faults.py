"""Scratch (not committed): the two faults of the laguna family, put into
a program the adapter built, between the build and the first step; no
switch in the program.  Used by _archive/pr44_controls.py (the chip, the
cell's size) and _archive/pr44_toy_controls.py (the CPU, the toy)."""
import copy


def ops_of(program):
    return [getattr(layer, layer._operator)
            for layer in program.model.model.layers]


def rotate_whole_heads(program):
    """The full layers rotate all of a head: their table is made at
    partial_rotary_factor 1 (YaRN's blend then runs over the whole
    head's pairs, as a reader who missed the factor would make it)."""
    from paddle_tpu.models.mellum import RopeTables
    n = 0
    for op in ops_of(program):
        if op.kind != "full_attention":
            continue
        cfg = copy.copy(op._tables.cfg)
        cfg.rope_parameters = {
            k: {**v, "partial_rotary_factor": 1}
            for k, v in cfg.rope_parameters.items()}
        assert op._tables.width(op.kind) == op.head_dim // 2
        op._tables, n = RopeTables(cfg), n + 1
    return n


def leave_the_gate_out(program):
    """Every gate reads one: the projection's result is replaced by 30
    before the sigmoid (1 - 1e-13, one in bfloat16 and float32)."""
    n = 0
    for op in ops_of(program):
        lin = op.g_proj
        plain = type(lin).forward
        lin.forward = lambda x, lin=lin, plain=plain: plain(lin, x) * 0 + 30.0
        n += 1
    return n


FAULTS = {"whole_head_rotation": rotate_whole_heads,
          "gate_left_out": leave_the_gate_out}
