from bench.harness.readers import for_family, predict_roofline

read = for_family(predict_roofline, "decode")
