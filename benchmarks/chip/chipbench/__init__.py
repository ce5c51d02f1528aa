"""The chip benchmark's own code: traffic, shapes, trace reduction,
the open-loop client and the output check."""
