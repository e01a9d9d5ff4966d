"""IMDB LSTM benchmark config (reference ``benchmark/paddle/rnn/rnn.py``).

Baseline rows (reference benchmark/README.md:124-126, bs=128, 1× K40m):
hidden 256 → 110 ms/batch, 512 → 261 ms, 1280 → 1007 ms.  Run this
config with ``--config_args hidden_size=1280`` for the big-hidden row
(past the single-block kernel's VMEM gate: the hidden-blocked tier,
``chip_smoke.py --all``'s ``lstm1280`` phase).
"""

num_class = 2
vocab_size = 30000
fixedlen = 100
batch_size = get_config_arg('batch_size', int, 128)
lstm_num = get_config_arg('lstm_num', int, 2)
hidden_size = get_config_arg('hidden_size', int, 512)
pad_seq = get_config_arg('pad_seq', bool, True)

args = {'vocab_size': vocab_size, 'pad_seq': pad_seq, 'maxlen': fixedlen}
define_py_data_sources2(None, None, module="provider", obj="process_rnn",
                        args=args)

settings(
    batch_size=batch_size,
    learning_rate=2e-3,
    learning_method=AdamOptimizer(),
    regularization=L2Regularization(8e-4),
    gradient_clipping_threshold=25)

net = data("data", integer_value_sequence(vocab_size))
net = embedding(net, size=128)
from paddle_tpu.v2.networks import simple_lstm
for i in range(lstm_num):
    net = simple_lstm(net, size=hidden_size, name=f"lstm{i}")
net = last_seq(net)
net = fc(net, size=num_class, act=SoftmaxActivation())
lab = data("label", integer_value(num_class))
loss = classification_cost(net, lab)
outputs(loss)
