"""The chip benchmark's own code: the yardstick that later changes to the
program are measured with.

Nothing here is imported by the program.  From the program the
benchmark takes only the system under test (``Trainer``, ``Llog``,
``LcapCluster``, ``connect``) and wraps its calls in spans of its own.
"""
