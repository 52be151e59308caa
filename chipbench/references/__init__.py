"""Plain references of the kernels the configurations run: the loop body
of each kernel written out in numpy over a batch, with no mapping,
lowering, engine or kernel in between.  Each module exposes
``run(inputs, n_iters, bits=32)``; ``bits`` is the datapath width the
arithmetic wraps at (32 as configured, 16 for the control)."""
