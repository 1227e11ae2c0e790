from bench.harness.readers import mfu, for_family

read = for_family(mfu, "decode")
