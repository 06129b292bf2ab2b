"""One module per model family, found by the ``family`` key of a
configuration's file (``spec.family``). A family answers what the harness
has to know of a model: the program's entry, the weights' tree, the plain
reference, the work counted and the rehearsal's size."""
