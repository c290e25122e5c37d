"""Arrival processes, one module each, found by the ``arrival`` name of
a traffic mix. A module gives:

``count(traffic, seconds)``     requests in a stream of ``seconds``
``offsets(traffic, n, order)``  their due times from the stream's start,
                                ordered by the generator ``order``
``Loop``                        how they reach the engine: a class of
                                :mod:`chipbench.loops`
"""
