"""Circuit generators, one file a generator, found by the name a
configuration's `circuit.generator` gives. Each has

    r1cs(params) -> bytes                      the circuit, as a .r1cs file
    witness(params, pool_seed, i) -> (bytes, [int])
                                               the i-th witness of the pool
                                               as a .wtns file, and its
                                               public inputs

and builds them with the program's own front end (`frontend/`), as a user
of the service would with circom."""
