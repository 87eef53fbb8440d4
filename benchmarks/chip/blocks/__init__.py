"""Model blocks, one module per kind of block, named by the ``block`` key
of a configuration file and resolved by file (``model.block``).

A block module describes how the program runs a configuration of its
kind and what one of its decode steps costs:

* ``program_config(c, *, serving)`` — the program's ``ModelConfig`` for
  configuration ``c`` and a cell's serving geometry; raises
  ``ValueError`` for a configuration the program cannot run as stated;
* ``weight_shapes(c)`` — ``{path: (shape, dtype, init)}`` of every leaf
  in the program's parameter layout (``model.make_weights`` makes them);
* ``matmul_params(c)`` — parameters that take part in a matrix product
  for every token;
* ``layer_weights(weights, i)`` — layer ``i``'s leaves, where the
  block's plain reference reads them;
* ``step(c, decode_lengths, itemsize)`` — ``(flops, bytes)`` of one
  decode step at the given context lengths.  Operations and bytes are
  counted apart: a step may read other weight bytes than its tokens
  multiply by (experts), and a layer may read state rather than cache.
"""
