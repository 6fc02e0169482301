"""The fused trainer's state made on the device: parameters, optimizer and
environment state (`train.run_fused`'s `init_state` span, which waits for
the device while the program is traced; benchmark/startup.py)."""
LAYER, UNIT, SOURCE = "CLI / drivers", "ms", "program_span"
MOVES = "setup_s"


def read(run, ctx):
    from benchmark import startup

    return startup.span_ms(run, "init_state")
