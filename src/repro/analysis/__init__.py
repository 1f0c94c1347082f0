"""Compiled-artifact analysis: loop-aware flops, bytes and collective wire
bytes of an HLO module (``hlo.analyze``)."""
