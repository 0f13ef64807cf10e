#!/bin/bash
# Discriminating real-data A/B on the HARDENED digits task (VERDICT r2
# #5 / weak #6): the stock digits-CIFAR task saturates ~.99 val on both
# arms and its 297-image val set quantizes at 0.34%, too coarse to
# separate the warm-kernel legs. This task is 300 train images with 30%
# train-label noise against a 600-image clean val set (0.17%
# quantization, generalization gap forced open), same unmodified
# reference recipe otherwise.
#
# Five 40-epoch legs, sequential, on the virtual CPU mesh (nd=4):
# SGD / cold eigen_dp / warm-NS inverse_dp / basis10 eigen_dp /
# warm-subspace eigen_dp — the same leg set as the round-2 evidence,
# now on a task that can actually rank them. TB scalars land under
# logs/tb_digits_hard/<leg> for plotting.
#
# Usage: nohup bash scripts/run_digits_hard_ab.sh > logs/digits_hard_ab.log 2>&1 &
# AB_SEED=<n> re-runs the whole ladder under a different trainer seed
# (init + shuffle; the dataset/noise split stays fixed) into
# logs/tb_digits_hard_s<n> — error bars across seeds (VERDICT r3 #8).

set -u
cd "$(dirname "$0")/.."
SEED=${AB_SEED:-42}
TB=logs/tb_digits_hard
[ "$SEED" != 42 ] && TB="logs/tb_digits_hard_s$SEED"
mkdir -p "$TB"

python scripts/make_digits_cifar.py /tmp/digits_hard \
    --train-n 300 --val-n 600 --label-noise 0.3

common=(data_dir=/tmp/digits_hard nworkers=4 batch_size=32 epochs=40
        lr_decay="25 35")

leg() {  # leg <name> <env...> -- <extra trainer args...>
  local name=$1; shift
  local envs=()
  while [ "$1" != "--" ]; do envs+=("$1"); shift; done
  shift
  echo "=== leg $name seed=$SEED $(date +%H:%M:%S)"
  env "${common[@]}" "${envs[@]}" JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      bash train_cifar10.sh --tb-dir "$TB/$name" --seed "$SEED" "$@" \
    || echo "=== leg $name FAILED rc=$?"
}

# AB_LEGS=ekfac runs only the E-KFAC ladder (appended round 4);
# AB_LEGS=trio runs the three-way amortization triangulation
# (cold eigen / plain basis10 / E-KFAC-corrected basis10) for extra
# seeds; default runs the original six legs
if [ "${AB_LEGS:-}" = "trio" ]; then
  leg cold_eigen     kfac=1 kfac_name=eigen_dp --
  leg basis10        kfac=1 kfac_name=eigen_dp basis_freq=10 --
  leg ekfac_b10_d3   kfac=1 kfac_name=ekfac_dp basis_freq=10 \
      -- --damping 0.3
elif [ "${AB_LEGS:-}" != "ekfac" ]; then
  leg sgd            kfac=0 --
  leg cold_eigen     kfac=1 kfac_name=eigen_dp --
  leg cold_chol      kfac=1 kfac_name=inverse_dp --
  leg warm_ns        kfac=1 kfac_name=inverse_dp -- --kfac-warm-start
  leg basis10        kfac=1 kfac_name=eigen_dp basis_freq=10 --
  leg warm_subspace  kfac=1 kfac_name=eigen_dp KFAC_EIGH_IMPL=subspace \
      -- --kfac-warm-start
else
  # E-KFAC on the real conv task: at the recipe damping, at its own
  # larger lambda (the MLP sweep preferred ~10x — its denominators are
  # exact second moments), and amortized-basis at that lambda
  leg ekfac          kfac=1 kfac_name=ekfac_dp --
  leg ekfac_d3       kfac=1 kfac_name=ekfac_dp -- --damping 0.3
  leg ekfac_b10_d3   kfac=1 kfac_name=ekfac_dp basis_freq=10 \
      -- --damping 0.3
fi

echo "=== digits-hard A/B complete $(date)"
python scripts/parse_logs.py logs/cifar10_*digits_hard*.log 2>/dev/null \
  || true
