"""Static-graph training + inference export: the reference's classic
Program/Executor workflow, end to end.

    python examples/static_mnist.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# examples demo on CPU devices by default, at toy sizes;
# run with PADDLE_TPU_EXAMPLE_BACKEND=native for real chips
if os.environ.get("PADDLE_TPU_EXAMPLE_BACKEND", "cpu") == "cpu":
    from paddle_tpu.device import pin_cpu
    assert pin_cpu(1), "could not pin the CPU backend"

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.static as static


def main():
    paddle.enable_static()
    main_prog, startup = static.Program(), static.Program()
    with static.program_guard(main_prog, startup):
        img = static.data("img", [-1, 784], "float32")
        label = static.data("label", [-1], "int64")
        hidden = static.nn.fc(img, 128, activation="relu")
        logits = static.nn.fc(hidden, 10)
        loss = paddle.nn.functional.cross_entropy(logits, label)
        paddle.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    exe = static.Executor()
    with static.program_guard(main_prog, startup):
        exe.run(startup)

    rng = np.random.RandomState(0)
    X = rng.randn(256, 784).astype(np.float32)
    Y = rng.randint(0, 10, 256).astype(np.int64)
    for epoch in range(15):
        lv, = exe.run(main_prog, feed={"img": X, "label": Y},
                      fetch_list=[loss])
    print(f"final train loss: {float(lv):.4f}")

    # export the inference slice (training ops pruned) and serve it
    d = tempfile.mkdtemp()
    path = os.path.join(d, "mnist")
    static.save_inference_model(path, [img], [logits], exe,
                                program=main_prog)
    layer, feeds, fetches = static.load_inference_model(path, exe)
    out, = exe.run(layer, feed={"img": X[:5]}, fetch_list=fetches)
    print("served logits shape:", out.shape)

    from paddle_tpu.inference import Config, create_predictor
    pred = create_predictor(Config(path + ".pdmodel", path + ".pdiparams"))
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.copy_from_cpu(X[:3])
    pred.run()
    print("predictor output shape:",
          pred.get_output_handle(pred.get_output_names()[0])
          .copy_to_cpu().shape)
    paddle.disable_static()


if __name__ == "__main__":
    main()
